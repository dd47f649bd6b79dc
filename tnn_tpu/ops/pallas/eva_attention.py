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

Kernel (``tnn_eva_attention``): grid ``(B, H_kv / heads, table entries /
pages)``, ``(pages, heads)`` from ``paged_attention.fetch_group`` at
``GROUP_POSITIONS`` key positions a group, as the paged and the latent
kernels' (EvaByte: one page of all 32 heads in the decode form, one page of
8 heads under a chunk of 256). A grid step fetches ``pages`` consecutive
table entries of ``heads`` heads each (contiguous in the pool's layout, so
one DMA a page) and makes ONE running-softmax update a head over the group's
``pages * bs`` slots: one score product batched over the head block, one
mask, one ``m / l / acc`` update, one value product. The mask is a slot's
own: a group may straddle ``n_exact``. A group with no live slot is skipped
whole; dead pages repeat the last live page of their segment and dead groups
the group before them (``_fetch_table``), so their DMAs are elided.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_NEG_INF, _from_head_major, _gather_pages,
                              _to_head_major, fetch_group, write_rows)
from .runtime import interpret_default

# key positions a grid step attends over (``fetch_group``'s ``positions``).
# At EvaByte's shape (32 heads of 128 over pages of 128) 128 gives ONE page of
# all 32 heads a grid step: one contiguous 1 MB DMA for K, one for V, and no
# dead page is ever fetched; 256 (2 pages x 16 heads) and 512 (4 x 8) walk
# the same 256 grid steps a layer and read 9% and 30% slower (PERF.md, PR 50)
GROUP_POSITIONS = 128


def _kernel(tables_ref, elens_ref, slens_ref, qlens_ref, layer_ref, q_ref,
            *refs, scale, bs, g, qw, n_exact, pages):
    """One grid step: ``pages`` consecutive table entries of row b, every
    head of the step's head block, ONE running-softmax update a head over
    the group's ``pages * bs`` slots. ``refs``: per page slot its K and V
    block ``(heads, bs, Dh)``, the output, the m / l / acc scratch."""
    del layer_ref, tables_ref       # consumed by the index maps
    kv = refs[:2 * pages]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * pages:]
    b, j, nj = pl.program_id(0), pl.program_id(2), pl.num_programs(2)
    t = pages * bs
    split = n_exact * bs            # the table position of the first summary

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    elen, slen, q_live = elens_ref[b], slens_ref[b], qlens_ref[b]
    first = j * t                   # the table position of the group's first

    def load(slots):                # the group's K (or V): (heads, t, Dh)
        xs = [r[...] for r in slots]
        return xs[0] if pages == 1 else jnp.concatenate(xs, axis=1)

    # a group with no live slot is skipped whole (its fetches repeat the last
    # live group's, ``_fetch_table``); a group may straddle ``n_exact``
    @pl.when((first < elen) | ((first + t > split) & (first < split + slen)))
    def _group():
        q, k, v = q_ref[...], load(kv[0::2]), load(kv[1::2])
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        trow = jax.lax.broadcasted_iota(jnp.int32, (qw * g, 1), 0)
        if g > 1:
            trow = jax.lax.div(trow, jnp.int32(g))
        # slot ``pos`` of the table (exact positions, then summary rows): a
        # summary row is live below sum_len for every query of the step; an
        # exact row is causal: token t sits at window-relative position
        # elen - q_live + t. Rows past q_live see nothing
        limit = jnp.where(pos >= split, split + slen - 1,
                          elen - q_live + trow)
        mask = ((pos <= limit) & (trow < q_live))[None]     # (1, Q*g, t)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _final():
        l = l_scr[...]                              # noqa: E741
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def _fetch_table(tables, exact_lens, sum_lens, n_exact, bs, pages):
    """The table the kernel walks, ``pages`` entries a grid step: padded to
    whole groups, every dead entry replaced by the one its DMA should
    repeat. A dead page repeats the last live page of its segment (the first
    page of a segment with none), and a group with no live page repeats the
    group before it slot for slot: a block index that repeats lets the
    pipeline elide the DMA. (``paged_attention._fetch_table``'s rule over
    two segments; computed once a step program, not by every index map.)"""
    nb = tables.shape[1]
    width = -(-nb // pages) * pages
    e = np.arange(width, dtype=np.int32)[None, :]
    last_e = jnp.clip(jax.lax.div(exact_lens + (bs - 1), bs), 1,
                      n_exact)[:, None] - 1
    last_s = jnp.clip(jax.lax.div(sum_lens + (bs - 1), bs), 1,
                      max(nb - n_exact, 1))[:, None] - 1
    own = jnp.where(e < n_exact, jnp.minimum(e, last_e),
                    n_exact + jnp.minimum(e - n_exact, last_s))
    live = jnp.where(e < n_exact, e * bs < exact_lens[:, None],
                     (e - n_exact) * bs < sum_lens[:, None])
    group = np.arange(width // pages, dtype=np.int32)[None, :]
    src = jax.lax.cummax(jnp.where(
        live.reshape(-1, width // pages, pages).any(axis=2), group, 0),
        axis=1)
    at = jnp.take_along_axis(own, jnp.repeat(src, pages, axis=1) * pages
                             + e % pages, axis=1)
    return jnp.maximum(jnp.take_along_axis(tables, at, axis=1), 0)


def pages_fetched(exact_lens, sum_lens, n_exact, bs, pages):
    """``(live pages, page slots fetched)`` a row (numpy arrays) of a launch
    whose grid steps take ``pages`` table entries: the live pages of both
    segments, and ``pages`` slots for every group that holds one (a group
    that straddles ``n_exact`` with live pages of both counts once). The
    kernel's own rule (``_kernel``'s live groups), for the engine's
    ``attn_fetch_fill_mean``."""
    live_e = -(-np.asarray(exact_lens, np.int64) // bs)
    live_s = -(-np.asarray(sum_lens, np.int64) // bs)
    first_s = n_exact // pages          # the group of the first summary page
    groups = -(-live_e // pages) + np.where(
        live_s > 0, (n_exact + live_s - 1) // pages - first_s + 1, 0)
    groups -= (live_s > 0) & (live_e > 0) & ((live_e - 1) // pages == first_s)
    return live_e + live_s, groups * pages


# inlined for the reason ``_paged_attention_pallas`` is: one trace and one
# kernel lowering a step program, not one a layer
@functools.partial(jax.jit, inline=True,
                   static_argnames=("n_exact", "scale", "interpret",
                                    "positions"))
def _eva_attention_pallas(q, pages_k, pages_v, tables, exact_lens, sum_lens,
                          q_lens, layer, *, n_exact, scale, interpret,
                          positions):
    b, qw, h, dh = q.shape
    _, _, hkv, bs, _ = pages_k.shape
    g = h // hkv
    pages, heads = fetch_group(bs=bs, dh=dh, hkv=hkv, qg=qw * g,
                               page_dtype=pages_k.dtype, nb=tables.shape[1],
                               positions=positions)
    elens, slens = exact_lens.astype(jnp.int32), sum_lens.astype(jnp.int32)
    tables = _fetch_table(tables.astype(jnp.int32), elens, slens, n_exact,
                          bs, pages)
    nb = tables.shape[1]
    qg = _to_head_major(q, hkv)                     # (B, H_kv, Q*g, Dh)

    def kv_index(i):
        def index(bi, hi, j, tbl, el, sl, ql, ly):
            return (ly[0], tbl[bi, j * pages + i], hi, 0, 0)
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
                          n_exact=n_exact, pages=pages),
        name="tnn_eva_attention",       # what the device profile shows
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, qw * g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, elens, slens, q_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)), *operands)
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
                  interpret: Optional[bool] = None):
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
                jnp.asarray(layer, jnp.int32), n_exact=n_exact,
                scale=float(scale),
                interpret=interpret_default() if interpret is None
                else interpret, positions=GROUP_POSITIONS)
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
