"""Latent (MLA) attention over the paged pool (Pallas TPU) + the plain
``jax.numpy`` path.

A latent model caches ONE row a token a layer, ``[c_kv | k_rope | 0]``, with
no head axis: the pool's pages are ``(L, N, 1, bs, row)``, ``row`` the
latent width rounded up to whole 128-lane registers (:func:`row_width`), and
there is no value pool. In the absorbed form (``nn.attention.
LatentAttention.apply_paged``) every query head carries ``[q_nope W_uk^T |
q_rope | 0]``, so a cached row is the KEY (all of it) and the VALUE (its
first ``value_dim`` lanes) at once: to the kernel this is grouped-query
attention of one KV head and ``H`` query heads of width ``row``, whose value
is a slice of the SAME fetched block. ``tnn_mla_attention`` is
``paged_attention``'s grid step (``fetch_group``: consecutive table entries
of a row a step, one online-softmax update over the group, dead groups
skipped and their fetches elided through ``_fetch_table``) with that one
difference: a page is read once. It shares the page write
(``scatter_kv_chunk``), the masking rules and the head-major query layout.

With one KV head there are no further heads to fill a grid step with, so the
kernel asks ``fetch_group`` for a group of ``GROUP_POSITIONS`` key positions
(8 pages of 128) where ``paged_attention`` asks for 128; a chunk's queries
are cut into tiles of ``QUERY_ROWS`` rows (tokens x heads) that each walk
the row's pages, by handing the launch each tile as a batch row of its own.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_NEG_INF, _fetch_table, _from_head_major,
                              _to_head_major, fetch_group)
from .runtime import interpret_default

GROUP_POSITIONS = 1024      # key positions a grid step attends over
QUERY_ROWS = 512            # query rows (tokens x heads) of a grid step


def row_width(latent_dim: int) -> int:
    """Lanes of a latent page row: whole 128-lane registers (a row that does
    not fill the lanes rests in another layout than the kernel and the
    page write read, and every step program copies the pool)."""
    return -(-latent_dim // 128) * 128


def _kernel(tables_ref, lens_ref, qlens_ref, layer_ref, q_ref, *refs,
            scale, bs, g, qw, pages, dv):
    """One grid step: ``pages`` consecutive table entries of row b, each ONE
    block ``(bs, row)``, one online-softmax update over the group. ``refs``:
    the page slots, the output ``(Q*g, dv)``, the m / l / acc scratch."""
    del layer_ref
    kv, (o_ref, m_scr, l_scr, acc_scr) = refs[:pages], refs[pages:]
    b, j, nj = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    t = pages * bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len, q_live = lens_ref[b], qlens_ref[b]

    @pl.when(j * t < kv_len)
    def _group():
        q = q_ref[...]                                  # (Q*g, row)
        k = kv[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in kv], axis=0)               # (t, row)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        trow = jax.lax.broadcasted_iota(jnp.int32, (qw * g, 1), 0)
        if g > 1:
            trow = jax.lax.div(trow, jnp.int32(g))
        # token t of the row sits at kv_len - q_live + t: causal over its own
        # chunk and every earlier position; rows past q_live see no key
        limit = jnp.where(trow < q_live, kv_len - q_live + trow, -1)
        mask = kpos <= limit                            # (Q*g, t)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # the value is the first dv lanes of the SAME block
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(k.dtype), k[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _final():
        l = l_scr[...]  # noqa: E741
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def query_tile(qw: int, h: int) -> int:
    """Tokens of a step's ``qw`` a grid step takes: the largest power-of-two
    divisor of ``qw`` whose ``tokens * h`` rows are at most ``QUERY_ROWS``."""
    qt = qw
    while qt > 1 and qt % 2 == 0 and qt * h > QUERY_ROWS:
        qt //= 2
    return qt


def _tile_rows(q, tables, kv_lens, q_lens, qt):
    """Cut each row's ``Q`` tokens into ``Q / qt`` batch rows of ``qt``:
    tile i of row b holds tokens ``i * qt ..``, of which ``clip(q_len - i *
    qt, 0, qt)`` are live, and ends at the position its last live token
    does (0: a dead row). The kernel's ragged rule (token t of a row sits
    at ``kv_len - q_len + t``) then places every token where it was."""
    b, qw, h, d = q.shape
    n = qw // qt
    first = jnp.arange(n, dtype=jnp.int32)[None, :] * qt        # (1, n)
    live = jnp.clip(q_lens[:, None] - first, 0, qt)             # (B, n)
    ends = jnp.where(live > 0,
                     (kv_lens - q_lens)[:, None] + first + live, 0)
    return (q.reshape(b * n, qt, h, d), jnp.repeat(tables, n, axis=0),
            ends.reshape(-1), live.reshape(-1))


# inlined for the reason ``_paged_attention_pallas`` is: one trace and one
# kernel lowering a step program, not one a layer
@functools.partial(jax.jit, inline=True,
                   static_argnames=("scale", "interpret", "dv"))
def _mla_attention_pallas(q, pages, block_tables, kv_lens, q_lens, layer, *,
                          scale, interpret, dv):
    b0, qw0, h, row = q.shape
    qt = query_tile(qw0, h)
    if qt != qw0:
        q, block_tables, kv_lens, q_lens = _tile_rows(
            q, block_tables, kv_lens, q_lens, qt)
    b, qw = q.shape[:2]
    bs = pages.shape[-2]
    n_pages, _ = fetch_group(bs=bs, dh=row, hkv=1, qg=qw * h,
                             page_dtype=pages.dtype, nb=block_tables.shape[1],
                             positions=GROUP_POSITIONS)
    qg = _to_head_major(q, 1)                       # (B, 1, Q*h, row)
    lens = kv_lens.astype(jnp.int32)
    tables = _fetch_table(block_tables.astype(jnp.int32), lens, bs, n_pages)
    nb = tables.shape[1]

    def kv_index(i):
        def index(bi, j, tbl, ln, qln, ly):
            return (ly[0], jnp.maximum(tbl[bi, j * n_pages + i], 0), 0, 0, 0)
        return index

    def q_index(bi, j, tbl, ln, qln, ly):
        return (bi, 0, 0, 0)

    page_spec = [pl.BlockSpec((None, None, None, bs, row), kv_index(i))
                 for i in range(n_pages)]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, g=h, qw=qw,
                          pages=n_pages, dv=dv),
        name="tnn_mla_attention",       # what the device profile shows
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nb // n_pages),
            in_specs=[pl.BlockSpec((None, None, qw * h, row), q_index)]
            + page_spec,
            out_specs=pl.BlockSpec((None, None, qw * h, dv), q_index),
            scratch_shapes=[pltpu.VMEM((qw * h, 1), jnp.float32),
                            pltpu.VMEM((qw * h, 1), jnp.float32),
                            pltpu.VMEM((qw * h, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, qw * h, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lens, q_lens.astype(jnp.int32), jnp.reshape(layer, (1,)), qg,
      *([pages] * n_pages))
    return _from_head_major(out, qw).reshape(b0, qw0, h, dv)


def _mla_attention_xla(q, pages, block_tables, kv_lens, q_lens, layer, scale,
                       dv):
    """The same softmax in plain ``jax.numpy``: gather the row's pages into
    one run of keys, mask, softmax. What the CPU runs, and the kernel's
    parity oracle."""
    b, qw, h, row = q.shape
    bs = pages.shape[-2]
    t = block_tables.shape[1] * bs
    k = pages[layer, jnp.maximum(block_tables, 0), 0].reshape(b, t, row)
    s = jnp.einsum("bqhd,btd->bqht", q, k,
                   preferred_element_type=jnp.float32) * scale
    tpos = jnp.arange(qw)[None, :]
    limit = jnp.where(tpos < q_lens[:, None],
                      (kv_lens - q_lens)[:, None] + tpos, -1)       # (B, Q)
    live = jnp.arange(t)[None, None, :] <= limit[:, :, None]        # (B, Q, T)
    s = jnp.where(live[:, :, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(live, axis=-1)[:, :, None, None], p, 0.0)
    out = jnp.einsum("bqht,btd->bqhd", p.astype(k.dtype), k[..., :dv],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def mla_attention(q, pages, block_tables, kv_lens, *, value_dim: int,
                  q_lens=None, layer=0, scale: Optional[float] = None,
                  backend: str = "auto", interpret: Optional[bool] = None):
    """Ragged attention of absorbed latent queries over latent pages.

    q : (B, Q, H, row): row b carries ``q_lens[b]`` live tokens (default:
        all ``Q``), left-aligned; padding outputs exactly 0.
    pages : the pool's (L, N, 1, bs, row) latent pages, never copied.
    block_tables : (B, nb) page ids in logical order (scratch-padded).
    kv_lens : (B,) live positions a row INCLUDING this step's (written
        before the call); token t of row b sits at ``kv_lens[b] - q_lens[b]
        + t`` and attends causally.
    value_dim : the leading lanes of a row that are its value.
    backend : "pallas", "xla", or "auto" (the kernel on TPU, else
        ``jax.numpy``).

    Returns (B, Q, H, value_dim)."""
    if pages.ndim != 5 or pages.shape[2] != 1 \
            or pages.shape[-1] != q.shape[-1] or q.ndim != 4:
        raise ValueError(f"latent pages are (L, N, 1, bs, row) and queries "
                         f"(B, Q, H, row); got {pages.shape} / {q.shape}")
    b = q.shape[0]
    if q_lens is None:
        q_lens = jnp.full((b,), q.shape[1], jnp.int32)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    with jax.named_scope("mla_attn"):
        if backend == "xla":
            return _mla_attention_xla(q, pages, block_tables, kv_lens, q_lens,
                                      layer, scale, value_dim)
        if backend != "pallas":
            raise ValueError(f"unknown mla-attention backend {backend!r}")
        return _mla_attention_pallas(
            q, pages, block_tables, kv_lens, q_lens,
            jnp.asarray(layer, jnp.int32), scale=float(scale),
            interpret=interpret_default() if interpret is None else interpret,
            dv=value_dim)
