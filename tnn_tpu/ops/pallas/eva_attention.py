"""EVA attention over a pool of two kinds of page (Pallas TPU) + the plain
``jax.numpy`` path.

A row of a model with EVA attention (Zheng et al., arXiv:2302.04542, as
EvaByte has it: ``chipbench/reference/evabyte.py`` holds the equations) keeps
two kinds of state in the SAME pool arrays ``(L, N, H_kv, bs, Dh)``:

* exact pages: K/V of the positions of its CURRENT window only. Position
  ``p`` of window ``w = p // window`` lives at window-relative position
  ``p - w * window`` of the row's exact table;
* summary pages: one row ``(k~, v~)`` for every chunk of ``chunk`` tokens,
  row ``c`` of the row's summary table, written on the chunk's last token
  (:func:`write_summaries`) and read from the window after the chunk's own.

The step's block table carries both: ``tables[:, :n_exact]`` are the exact
pages, ``tables[:, n_exact:]`` the summary pages. :func:`eva_attention` runs
ONE softmax over the live exact positions (causal inside the step's chunk)
and the first ``sum_lens`` summary rows. With no summary pages and a window
that never ends it is plain paged attention (``paged_attention``), which
this kernel shares its layout helpers, page write and masking rules with.

Kernel (``tnn_eva_attention``): grid ``(B, H_kv / heads_per_step, table
entries / pages_per_step)``. A grid step fetches ``pages_per_step`` pages of
``heads_per_step`` heads each (contiguous in the pool's layout, so one DMA a
page) and folds them into the running softmax; dead pages clamp to the last
live page of their segment, so their DMAs are elided.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_NEG_INF, _from_head_major, _gather_pages,
                              _to_head_major, write_rows)
from .runtime import interpret_default

PAGES_PER_STEP = 4
HEADS_PER_STEP = 8


def _kernel(tables_ref, elens_ref, slens_ref, qlens_ref, layer_ref, q_ref,
            *refs, scale, bs, g, qw, n_exact, pages, heads):
    del layer_ref, tables_ref       # consumed by the index maps
    kv = refs[:2 * pages]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * pages:]
    b, j, nj = pl.program_id(0), pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    elen, slen, q_live = elens_ref[b], slens_ref[b], qlens_ref[b]
    for i in range(pages):
        e = j * pages + i                       # this page's table entry
        is_sum = e >= n_exact
        base = jnp.where(is_sum, e - n_exact, e) * bs
        k_ref, v_ref = kv[2 * i], kv[2 * i + 1]

        @pl.when(base < jnp.where(is_sum, slen, elen))
        def _page(is_sum=is_sum, base=base, k_ref=k_ref, v_ref=v_ref):
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, (qw * g, bs), 1)
            trow = jax.lax.broadcasted_iota(jnp.int32, (qw * g, 1), 0)
            if g > 1:
                trow = jax.lax.div(trow, jnp.int32(g))
            # a summary row is live below sum_len for every query of the
            # step; an exact row is causal: token t sits at window-relative
            # position elen - q_live + t
            limit = jnp.where(is_sum, slen - 1, elen - q_live + trow)
            mask = (kpos <= limit) & (trow < q_live)
            for h in range(heads):
                q, k, v = q_ref[h], k_ref[h], v_ref[h]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, _NEG_INF)
                m_prev, l_prev = m_scr[h], l_scr[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h] = m_new

    @pl.when(j == nj - 1)
    def _final():
        l = l_scr[...]                              # noqa: E741
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def _eva_attention_pallas(q, pages_k, pages_v, tables, exact_lens, sum_lens,
                          q_lens, n_exact, layer, scale, interpret,
                          pages_per_step, heads_per_step):
    b, qw, h, dh = q.shape
    _, _, hkv, bs, _ = pages_k.shape
    g = h // hkv
    heads = math.gcd(heads_per_step, hkv)
    pages = max(1, min(pages_per_step, tables.shape[1]))
    pad = -tables.shape[1] % pages
    if pad:     # whole steps: the padding lies past every summary length
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    nb = tables.shape[1]
    qg = _to_head_major(q, hkv)                     # (B, H_kv, Q*g, Dh)

    def kv_index(i):
        def index(bi, hi, j, tbl, el, sl, ql, ly):
            e = j * pages + i
            live_e = (jnp.maximum(el[bi], 1) + bs - 1) // bs
            live_s = (jnp.maximum(sl[bi], 1) + bs - 1) // bs
            at = jnp.where(e < n_exact, jnp.minimum(e, live_e - 1),
                           n_exact + jnp.minimum(e - n_exact, live_s - 1))
            return (ly[0], jnp.maximum(tbl[bi, jnp.minimum(at, nb - 1)], 0),
                    hi, 0, 0)
        return index

    def q_index(bi, hi, j, tbl, el, sl, ql, ly):
        return (bi, hi, 0, 0)

    in_specs = [pl.BlockSpec((None, heads, qw * g, dh), q_index)]
    operands = [qg]
    for i in range(pages):
        spec = pl.BlockSpec((None, None, heads, bs, dh), kv_index(i))
        in_specs += [spec, spec]
        operands += [pages_k, pages_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hkv // heads, nb // pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, heads, qw * g, dh), q_index),
        scratch_shapes=[pltpu.VMEM((heads, qw * g, 1), jnp.float32),
                        pltpu.VMEM((heads, qw * g, 1), jnp.float32),
                        pltpu.VMEM((heads, qw * g, dh), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, g=g, qw=qw,
                          n_exact=n_exact, pages=pages, heads=heads),
        name="tnn_eva_attention",       # what the device profile shows
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, qw * g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), exact_lens.astype(jnp.int32),
      sum_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), *operands)
    return _from_head_major(out, qw)


def _eva_attention_xla(q, pages_k, pages_v, tables, exact_lens, sum_lens,
                       q_lens, n_exact, layer, scale):
    """The same softmax in plain ``jax.numpy``: gather both tables into one
    contiguous run of keys, mask, softmax. What the CPU tests run and the
    kernel's parity oracle (head-major rows, as ``paged_attention``'s)."""
    b, qw, h, dh = q.shape
    _, _, hkv, bs, _ = pages_k.shape
    g = h // hkv
    t = tables.shape[1] * bs
    k = _gather_pages(pages_k, tables, layer, b, hkv, t, dh)
    v = _gather_pages(pages_v, tables, layer, b, hkv, t, dh)
    s = jnp.einsum("bhrd,bhtd->bhrt", _to_head_major(q, hkv), k,
                   preferred_element_type=jnp.float32) * scale
    start = (exact_lens - q_lens)[:, None]                # (B, 1)
    tpos = jnp.repeat(jnp.arange(qw), g)[None, :]         # (1, Q*g)
    kpos = jnp.arange(t)[None, None, :]
    exact = (kpos < n_exact * bs) & (kpos <= (start + tpos)[:, :, None])
    summ = (kpos >= n_exact * bs) \
        & (kpos - n_exact * bs < sum_lens[:, None, None])
    live = (exact | summ) & (tpos < q_lens[:, None])[:, :, None]
    s = jnp.where(live[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(live, axis=-1)[:, None, :, None], p, 0.0)
    out = jnp.einsum("bhrt,bhtd->bhrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return _from_head_major(out.astype(q.dtype), qw)


def eva_attention(q, pages_k, pages_v, tables, exact_lens, sum_lens, *,
                  n_exact: int, q_lens=None, layer=0,
                  scale: Optional[float] = None, backend: str = "auto",
                  interpret: Optional[bool] = None,
                  pages_per_step: int = PAGES_PER_STEP,
                  heads_per_step: int = HEADS_PER_STEP):
    """One softmax over a row's exact pages and its summary pages.

    q : (B, H, Dh), the decode form, or (B, Q, H, Dh) with ``q_lens[b]`` live
        tokens a row (left-aligned; padding outputs exactly 0).
    pages_k / pages_v : the pool's (L, N, H_kv, bs, Dh) arrays, never copied.
    tables : (B, n_exact + n_summary) page ids: the exact pages of the row's
        current window, then its summary pages (module docstring).
    exact_lens : (B,) live window-relative positions INCLUDING this step's
        rows (written before the call); token t of row b sits at
        ``exact_lens[b] - q_lens[b] + t`` and attends causally.
    sum_lens : (B,) summary rows the row may read (chunks of EARLIER windows).
    backend : "pallas", "xla", or "auto" (the kernel on TPU, else
        ``jax.numpy``).
    """
    was_3d = q.ndim == 3
    if was_3d:
        q = q[:, None]
    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not 0 < n_exact <= tables.shape[1]:
        raise ValueError(f"n_exact {n_exact} of {tables.shape[1]} table "
                         "entries")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    with jax.named_scope("eva_attn"):
        if backend == "xla":
            out = _eva_attention_xla(q, pages_k, pages_v, tables, exact_lens,
                                     sum_lens, q_lens, n_exact, layer, scale)
        elif backend == "pallas":
            out = _eva_attention_pallas(
                q, pages_k, pages_v, tables, exact_lens, sum_lens, q_lens,
                n_exact, layer, scale,
                interpret_default() if interpret is None else interpret,
                pages_per_step, heads_per_step)
        else:
            raise ValueError(f"unknown eva-attention backend {backend!r}")
    return out[:, 0] if was_3d else out


@jax.named_scope("eva_summarise")
def write_summaries(pages_k, pages_v, tables, starts, q_lens, phi, mu, *,
                    n_exact: int, window: int, chunk: int, layer: int,
                    qw: int):
    """Write the summary row of every chunk that this step's tokens
    complete. Call AFTER the step's exact rows are in their pages.

    Row b wrote positions ``starts[b] .. starts[b] + q_lens[b] - 1`` (all in
    one window: the scheduler ends a grant at a window's end). Chunk ``c``
    is complete when position ``chunk * c + chunk - 1`` is among them; its
    ``chunk`` exact rows are read back from the row's exact pages, and

        a = softmax_s(k_s . phi)   k~ = sum_s a_s k_s + mu   v~ = sum_s a_s v_s

    land at row ``c`` of the row's summary pages (``tables[:, n_exact:]``)
    through the same row-tile write as the exact rows. Returns the pages.
    """
    bs = pages_k.shape[-2]
    b = tables.shape[0]
    nc = -(-qw // chunk)                # chunks a step can complete, a row
    c0 = starts // chunk                # the first that may complete
    n_done = (starts + q_lens) // chunk - c0
    r0 = (c0 * chunk) % window          # its first row, window-relative
    npg = -(-(nc * chunk + bs - math.gcd(bs, chunk)) // bs)
    entry = jnp.clip((r0 // bs)[:, None] + jnp.arange(npg), 0, n_exact - 1)
    blk = jnp.maximum(jnp.take_along_axis(tables, entry, axis=1), 0)

    def rows_of(pages):     # (B, H, nc, chunk, Dh) float32
        x = pages[layer, blk]                       # (B, npg, H, bs, Dh)
        h, dh = x.shape[2], x.shape[4]
        x = x.transpose(0, 2, 1, 3, 4).reshape(b, h, npg * bs, dh)
        x = jax.vmap(lambda xi, o: jax.lax.dynamic_slice_in_dim(
            xi, o, nc * chunk, axis=1))(x, r0 % bs)
        return x.reshape(b, h, nc, chunk, dh).astype(jnp.float32)

    k, v = rows_of(pages_k), rows_of(pages_v)
    a = jax.nn.softmax(jnp.einsum("bhcsd,hd->bhcs", k,
                                  phi.astype(jnp.float32)), axis=-1)
    ks = jnp.einsum("bhcs,bhcsd->bhcd", a, k) \
        + mu.astype(jnp.float32)[None, :, None, :]
    vs = jnp.einsum("bhcs,bhcsd->bhcd", a, v)
    sum_tables = tables[:, n_exact:]
    pages_k = write_rows(
        pages_k, sum_tables, c0, ks.transpose(0, 2, 1, 3).astype(
            pages_k.dtype), n_done, layer=layer)
    pages_v = write_rows(
        pages_v, sum_tables, c0, vs.transpose(0, 2, 1, 3).astype(
            pages_v.dtype), n_done, layer=layer)
    return pages_k, pages_v
