"""Ragged paged-attention kernel (Pallas TPU) + XLA-lax reference.

The serving engine's attention hot path (arXiv:2604.15464's storage model):
each request's KV cache lives in fixed-size pages of the pool arrays

    pages_k, pages_v : (L, num_blocks, H_kv / p, block_size, p * head_dim)

(``p`` KV heads side by side in a page row so that a row fills the 128
lanes, ``lane_pack``; ``p`` = 1 is the plain ``(L, N, H_kv, bs, Dh)``, and
"head" below means a page row: to the kernel a packed pool is a
grouped-query pool of ``H_kv / p`` heads of ``p * Dh``, ``paged_attention``)
and a per-request *block table* names its pages in logical order. This
kernel consumes the pages DIRECTLY: the block tables and per-row
kv lengths are scalar-prefetched, the BlockSpec index maps chase the tables,
and flash-style online softmax accumulates over the streamed pages — so the
only KV traffic per step is the KV actually attended over, and no contiguous
cache ever exists.

Queries are RAGGED MULTI-TOKEN: each row carries ``q_lens[b]`` live query
tokens (1 for a decode row, up to the padded chunk width for a prefill
chunk), already scattered into the row's pages, so row b's token t sits at
absolute position ``kv_lens[b] - q_lens[b] + t`` and attends causally against
its own chunk plus every previously written position. ``q_lens = 1``
reproduces the PR 2 decode kernel exactly; this is what lets the engine pack
decode rows and prefill chunks into ONE compiled mixed step.

Grid: ``(B, H_kv / heads, ceil(num_table_entries / pages))``. A grid step
fetches ``pages`` CONSECUTIVE table entries of row b, each as one whole-page
block ``(heads, bs, Dh)`` (the pool is handed to ``pallas_call`` once per page
slot, each with its own index map into the block table; the ``heads`` heads
of a page are contiguous in the pool, so one DMA a page), and makes ONE
online-softmax update per head over the group's ``pages * bs`` positions:
one batched score matmul, one mask, one ``m / l / acc`` update, one
``p @ V``. The innermost axis sweeps the row's table a group at a time and
the (m, l, acc) scratch carries the softmax across it. ``fetch_group`` gives
``(pages, heads)`` from what a call can see: ``pages`` so that a group is
about 128 positions (no more than the table is wide), ``heads`` the largest
divisor of ``H_kv`` whose blocks, scratch and values fit a VMEM budget at the
page's shape and dtype and the query tile's ``Q * g`` rows. GPT-2 large
(pages of 16 x 64 bf16, 20 heads) runs 8 pages of all 20 heads a grid step:
a grid step costs ~0.3 us whatever it holds, so it has to hold a lot.
Because a head block never mixes heads, tensor-parallel serving
(``serving/tp.py``) runs this kernel UNMODIFIED per shard: each shard's pool
slice holds ``H_kv/tp`` heads of every page, the kernel sweeps it with the
same block tables (replicated host-side), and ``heads`` follows the shape.
Grouped-query attention shares each fetched kv page across its query group:
the wrapper lays q (and out, and the stats) out HEAD-MAJOR, (B, H_kv, Q * G,
Dh) with row ``t * G + i`` = token t, group member i, so a head's block is
already the 2-D (Q * G, Dh) tile the body multiplies — the v5e Mosaic
refuses in-kernel shape casts between (Q, G, Dh) and (Q * G, Dh). For the
decode form (Q = 1) that layout is a pure reshape; a multi-token step pays
one transpose of the small q/out tensors per call, never of KV. A group with
no live position is skipped whole (``pl.when``) and its fetch indices repeat
the row's last live group's, so the Pallas pipeline elides the dead DMAs
(same trick as flash_attention's causal dead-block clamp); dead pages INSIDE
a live group (past the row's length, or ``-1`` holes) fetch the row's last
live page (or page 0) and the mask keeps them out of the softmax.

``paged_attention_reference`` is the same math in plain lax (gather the tables
into a contiguous cache, masked softmax) — the parity oracle for the kernel
and the CPU/interpret fallback the router picks off-TPU, mirroring how
``flash_attention`` routes. ``scatter_kv_rows`` / ``scatter_kv_chunk`` are the
write half of the page contract: the new KV rows per sequence per step, stored
by ``tnn_kv_row_write``, which moves the sublane tile of the page that holds a
row and nothing else (``write_rows``).

INT8 PAGES (``QuantPages``): decode is HBM-bandwidth-bound on KV bytes, so
the pool may store pages as int8 with a per-(position, head) f32 scale
sidecar riding alongside (same block ids, same layout, Dh collapsed to 1).
The scatters quantize rows symmetrically at write time (``quantize_kv_rows``
— the same scale = amax/127 rule as ``nn.attention``'s per-model int8
cache), and both consumers dequantize at READ: the kernel inside its
online-softmax loop (K/V HBM traffic stays int8 bytes + one f32 scale per
row; compute-dtype K/V never exists in HBM), the XLA reference at its
gather. Quantized attention is gated by closeness, not bit-exactness — the
f32 code paths below are byte-untouched.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

_NEG_INF = -1e30


class QuantPages(NamedTuple):
    """Int8 KV pages + per-(position, head) f32 scale sidecar.

    ``data`` is the pool page array quantized to int8, ``scale`` the same
    layout with the head_dim axis collapsed to 1 — scale[l, n, h, s, 0]
    dequantizes row data[l, n, h, s, :]. A NamedTuple is a pytree, so the
    bundle flows through jit (``donate_argnums`` donates BOTH buffers) and
    through ``pool.update_pages`` unchanged; the two arrays share one
    block-id space, so alloc/free/fork/evict bookkeeping needs no second
    ledger.
    """
    data: jax.Array    # (L, N, H_kv, bs, Dh) int8
    scale: jax.Array   # (L, N, H_kv, bs, 1)  float32


def quantize_kv_rows(x):
    """Symmetric per-row (per position, per head) int8 over the last axis:
    scale = amax/127 — the same quantizer as ``nn.attention``'s per-model
    int8 cache, so pool-int8 and cache-int8 closeness gates measure the
    same arithmetic. Returns (int8 values, f32 scales with last axis 1)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                        1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


# a grid step's group: about this many key positions (the width of the MXU and
# of a vector register), every KV head of the pool where VMEM allows
_GROUP_POSITIONS = 128
# what a grid step's blocks (two buffers each), scratch and the body's values
# may take of the 16 MiB a kernel gets on the v5e by default
_VMEM_BUDGET = 10 * 2 ** 20


def _sublanes(dtype):
    """Rows of one vector register of ``dtype``: 8 of 4 bytes, 16 of 2, 32
    of 1."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _tile_bytes(rows, cols, dtype):
    """VMEM bytes of a (rows, cols) tile: lanes padded to 128, sublanes to
    the dtype's packing (``_sublanes``)."""
    sub = _sublanes(dtype)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 \
        * jnp.dtype(dtype).itemsize


def query_tile(qg, dtype):
    """Rows of one QUERY TILE of a row's ``qg = Q * g`` query rows of
    ``dtype``: one register's sublanes (16 rows of bf16, 8 of float32) where
    they divide ``qg``, else all ``qg`` (one tile: every decode program's
    ``1 * g``). A grid step of a row whose live query rows fit the first tile
    computes that tile alone (``_attn_kernel``). From the shape and the
    queries' dtype alone: the launch and the engine's
    ``attn_query_tile_share`` (``query_tiles_computed``) both ask this
    function."""
    sub = _sublanes(dtype)
    return sub if qg % sub == 0 else qg


def query_tiles_computed(q_lens, g, qg, tile):
    """The query tiles ``_attn_kernel`` computes a row (a numpy array),
    of the ``qg / tile`` a row of the launch holds, for rows of ``q_lens``
    live tokens of ``g`` query rows each: none for an empty row (no key is
    live either), ONE where the live rows fit a tile, else all of them."""
    live = np.asarray(q_lens, np.int64) * g
    return np.where(live > tile, qg // tile, np.minimum(live, 1))


def group_vmem_bytes(pages, heads, *, bs, dh, qg, page_dtype):
    """VMEM one grid step holds for a group of ``pages`` pages of ``heads``
    heads: the K/V blocks (and an int8 pool's scale sidecars) and the q/out
    blocks twice each (the pipeline's two buffers), the m/l/acc scratch, and
    the body's group-wide K, V, scores and probabilities. q is counted at
    the pages' dtype (float32 beside int8 pages)."""
    quant = jnp.dtype(page_dtype) == jnp.int8
    dtype = jnp.float32 if quant else page_dtype
    page = _tile_bytes(bs, dh, page_dtype)
    if quant:
        page += _tile_bytes(bs, 1, jnp.float32)
    t = pages * bs
    blocks = 2 * 2 * pages * page + 2 * 2 * _tile_bytes(qg, dh, dtype)
    scratch = _tile_bytes(qg, dh, jnp.float32) \
        + 2 * _tile_bytes(qg, 1, jnp.float32)
    body = 2 * _tile_bytes(t, dh, dtype) \
        + 2 * _tile_bytes(qg, t, jnp.float32) \
        + _tile_bytes(qg, dh, jnp.float32)
    return heads * (blocks + scratch + body)


def fetch_group(*, bs, dh, hkv, qg, page_dtype, nb,
                positions=_GROUP_POSITIONS):
    """``(pages, heads)`` of one grid step, from what a call can see: the
    page's shape ``(bs, dh)`` and dtype (int8 means ``QuantPages``), the
    pool's (or the shard's) ``hkv`` heads, the query tile's ``qg = Q * g``
    rows and the table's ``nb`` entries.

    ``pages`` consecutive table entries make a group of about
    ``positions`` key positions (a whole table if it is shorter; a pool of
    ONE head, ``mla_attention``'s, asks for more than the default: it has no
    further heads to fill a grid step with);
    ``heads`` is the largest divisor of
    ``hkv`` whose group fits ``_VMEM_BUDGET`` (``group_vmem_bytes``), and
    only a group too large with ONE head gives pages up. Three launches ask
    this function (``_launch`` here, ``mla_attention``'s, ``eva_attention``'s
    over its table of two segments), and the engine's ``attn_fetch_fill_mean``
    asks it for whichever the step runs (``InferenceEngine._attn_group``)."""
    pages = max(1, min(positions // bs, nb))
    size = functools.partial(group_vmem_bytes, bs=bs, dh=dh, qg=qg,
                             page_dtype=page_dtype)
    while pages > 1 and size(pages, 1) > _VMEM_BUDGET:
        pages //= 2
    heads = max(h for h in range(1, hkv + 1) if hkv % h == 0
                and (h == 1 or size(pages, h) <= _VMEM_BUDGET))
    return pages, heads


def lane_pack(hkv, dh, page_dtype):
    """How many KV heads of ``dh`` lie side by side in one page row: the
    largest divisor of ``hkv`` (the heads ONE device holds: a tensor-parallel
    shard's, so that a shard owns whole groups) that is at most ``128 // dh``.
    A row of ``lane_pack * dh`` then fills the 128 lanes of a vector
    register, and the pool rests in the layout the kernel and the page
    write read: at ``dh`` = 64 a row of one head is half a register, the
    compiler keeps such a pool in another layout, and every step program
    converted it in and out (4 whole-pool copies, three quarters of GPT-2
    large's decode step). 1 at ``dh`` >= 128, for an odd head count, and for
    int8 pages, whose f32 scale sidecar is one value a (position, head).
    From shapes and the page dtype alone: ``PagedKVPool`` asks it once, and
    the write and the read see it in ``pages.shape[-1] // rows.shape[-1]``."""
    if jnp.dtype(page_dtype) == jnp.int8:
        return 1
    return max(p for p in range(1, max(128 // dh, 1) + 1) if hkv % p == 0)


def _head_slot(h, p, g):
    """(H, 1): the slot of a packed row that query head ``h``'s KV head
    ``h // g`` lies in."""
    return ((np.arange(h) // g) % p)[:, None]


def _lay_in_lanes(q, p, g):
    """(..., H, Dh) -> (..., H, p * Dh): query head ``h`` of KV head
    ``h // g`` keeps its values in the lanes of that head's slot
    ``(h // g) % p`` of a packed row, zeros in the others. The packed pool
    is then a grouped-query pool of ``H_kv / p`` heads of ``p * Dh`` with a
    group of ``p * g``: a score against a packed key row is head ``h``'s own
    (the other heads' lanes meet exact zeros in a float32 accumulation)."""
    slot = _head_slot(q.shape[-2], p, g)
    return jnp.concatenate([jnp.where(slot == s, q, 0) for s in range(p)],
                           axis=-1)


def _own_lanes(out, p, g):
    """Inverse of ``_lay_in_lanes`` for the output: (..., H, p * Dh) ->
    (..., H, Dh), each head's own slot of its ``p @ V`` row (the other
    lanes hold its probabilities over the OTHER heads' values)."""
    dh = out.shape[-1] // p
    slot = _head_slot(out.shape[-2], p, g)
    own = out[..., :dh]
    for s in range(1, p):
        own = jnp.where(slot == s, out[..., s * dh:(s + 1) * dh], own)
    return own


def _over_packed_rows(attend, q, pages, stats):
    """``attend(q)`` over pages that hold ``p`` KV heads a row (``p`` read
    off the last dims): the queries laid into their lanes, each head's own
    lanes of the output kept (``m`` and ``l`` are a query head's: no lanes
    to choose). At ``p`` = 1 it is ``attend(q)``."""
    rows, width = _pages_shape(pages)[2::2]
    p = width // q.shape[-1]
    if p == 1:
        return attend(q)
    g = q.shape[-2] // (rows * p)
    out = attend(_lay_in_lanes(q, p, g))
    if stats:
        return (_own_lanes(out[0], p, g),) + tuple(out[1:])
    return _own_lanes(out, p, g)


def _attn_kernel(tables_ref, lens_ref, qlens_ref, layer_ref, q_ref, *refs,
                 scale: float, bs: int, g: int, qw: int, tq: int, pages: int,
                 quant: bool, stats: bool, window: Optional[int] = None):
    """One grid step: ``pages`` consecutive table entries of row b, every
    head of the step's head block, ONE online-softmax update per head over
    the group's ``pages * bs`` positions.

    ``refs``: per page slot its K and V block ``(heads, bs, Dh)`` (then the
    two ``(heads, bs, 1)`` scales of an int8 pool), the output, with
    ``stats`` the m and l outputs (the running max and normalizer a
    sequence-parallel shard hands ``ops.softmax_merge.merge_psum``), then
    the m / l / acc scratch, all ``(heads, Q*g, .)``. bf16 and int8 pages
    differ ONLY in how a page's K/V reaches the MXU (``load``). With
    ``window`` a query sees its own position and the ``window - 1`` before
    it: one more comparison in the mask (positions here are relative to the
    first page the launch walks, ``_launch``)."""
    del layer_ref  # consumed by the index maps, not the body
    per = 4 if quant else 2
    kv, refs = refs[:per * pages], refs[per * pages:]
    if stats:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        (o_ref, m_scr, l_scr, acc_scr), m_ref, l_ref = refs, None, None
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    t = pages * bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)   # running max
        l_scr[...] = jnp.zeros_like(l_scr)            # running denominator
        acc_scr[...] = jnp.zeros_like(acc_scr)        # output accumulator

    kv_len = lens_ref[b]
    q_live = qlens_ref[b]

    def load(i):
        k_ref, v_ref = kv[per * i], kv[per * i + 1]
        if not quant:
            return k_ref[...], v_ref[...]
        # dequantized in VMEM: the page arrives as int8 + one f32 scale per
        # row, so HBM traffic is int8 bytes. (int8's minimum TPU tile is
        # (32, 128); smaller pages lean on Mosaic's relayout.)
        return (k_ref[...].astype(jnp.float32) * kv[per * i + 2][...],
                v_ref[...].astype(jnp.float32) * kv[per * i + 3][...])

    # a group with no live position is skipped whole; dead pages INSIDE a
    # live group (past kv_len, or -1 holes) are fetched (_fetch_table says
    # what for them) and masked out of the softmax
    @pl.when(j * t < kv_len)
    def _group():
        def keys_values():
            ks, vs = zip(*(load(i) for i in range(pages)))
            return (ks[0] if pages == 1 else jnp.concatenate(ks, axis=1),
                    vs[0] if pages == 1 else jnp.concatenate(vs, axis=1))

        def update(q, k, v, rows=...):
            """The softmax update of the query rows ``q``, which are rows
            ``rows`` of the scratch from its first on (all by default)."""
            s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32) * scale
            # the key position of each of the group's slots. A NEGATIVE table
            # entry is a dead hole (sequence-parallel serving stamps -1 on the
            # pages another shard owns): its keys go past every query's limit
            slot = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
            entry = tables_ref[b, j * pages]
            for i in range(1, pages):
                entry = jnp.where(slot >= i * bs, tables_ref[b, j * pages + i],
                                  entry)
            kpos = jnp.where(entry < 0, 2 ** 30, j * t + slot)
            # query token t sits at absolute position start + t with
            # start = kv_len - q_live: causal over its own chunk AND over every
            # previously written position (q_live = 1 degenerates to the decode
            # mask kpos < kv_len); rows past q_live see no key at all
            trow = jax.lax.broadcasted_iota(jnp.int32, (q.shape[1], 1), 0)
            if g > 1:
                trow = jax.lax.div(trow, jnp.int32(g))
            limit = jnp.where(trow < q_live, kv_len - q_live + trow, -1)
            mask = kpos <= limit
            if window is not None:
                # the page that straddles a query's lower bound is fetched and
                # masked; pages wholly before the FIRST query's are never walked
                mask = mask & (kpos > limit - window)
            mask = mask[None]                               # (1, rows, t)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev, l_prev = m_scr[rows], l_scr[rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rows] = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
            acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_scr[rows] = m_new

        # (heads, Q*g, Dh), row t*g + i = token t, group member i: the
        # WRAPPER lays q/out/stats out that way, because the v5e Mosaic
        # refuses the (Q, g, Dh) <-> (Q*g, Dh) shape casts in-kernel
        if tq == qw * g:
            # the queries first, then K and V: the order the one-tile body
            # always traced in (a decode program's text does not move)
            update(q_ref[...], *keys_values())
            return
        # a launch wider than one query tile (``query_tile``): K and V once
        # a grid step, then ONE of two bodies by the row's live query rows.
        # A row that fits the first tile (a decode row in a 64-wide mixed
        # step: 2 rows of 128; a short last chunk) computes that tile alone;
        # the other tiles keep l = 0 and acc = 0 and leave exactly 0, as a
        # dead row does. A longer row computes the whole ``Q * g`` at once
        k, v = keys_values()

        @pl.when(q_live * g <= tq)
        def _first_tile():
            first = (slice(None), slice(0, tq))
            update(q_ref[first], k, v, first)

        @pl.when(q_live * g > tq)
        def _whole():
            update(q_ref[...], k, v)

    @pl.when(j == nj - 1)
    def _final():
        l = l_scr[...]  # noqa: E741
        lsafe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> exactly 0
        o_ref[...] = (acc_scr[...] / lsafe).astype(o_ref.dtype)
        if stats:
            m_ref[...] = m_scr[...]
            l_ref[...] = l


def _fetch_table(tables, lens, bs, pages, shift=None, width=None):
    """The table the kernel walks, ``pages`` entries a grid step: padded to
    whole groups, and every dead entry replaced by the one its DMA should
    repeat. Dead trailing groups repeat the row's last live group and dead
    pages inside it the row's last live page: a block index that repeats lets
    the pipeline elide the DMA. With ``shift`` (B,) the walk starts at that
    entry of each row's table (``lens`` then counts from there) and is
    ``width`` entries long: a windowed row's pages wholly before its lower
    bound are not walked. ``max(len, 1)`` keeps fully-dead rows at
    entry 0. Live entries, ``-1`` holes among them, stay as they are.
    (Computed here, once a step program, and not by every page slot's index
    map at every grid step: the layers' equal computations merge into one.)"""
    nb = tables.shape[1]
    walk = nb if width is None else min(width, nb)
    width = -(-walk // pages) * pages
    last = jnp.clip(jax.lax.div(lens + (bs - 1), bs), 1, walk)[:, None] - 1
    e = np.arange(width, dtype=np.int32)[None, :]
    e = jnp.minimum(e // pages, jax.lax.div(last, pages)) * pages + e % pages
    e = jnp.minimum(e, last)
    if shift is not None:
        e = jnp.minimum(e + shift[:, None], nb - 1)
    return jnp.take_along_axis(tables, e, axis=1)


# An INLINED jit: a step program calls this once a layer with the same shapes
# (the layer is an operand), so the launch (a dozen and a half index maps and
# the body) is traced once a program and not once a layer, and the equal
# ``pallas_call`` equations are lowered once; every call keeps its own scope.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("scale", "interpret", "stats", "window",
                                    "positions"))
def _paged_attention_pallas(q, pages_k, pages_v, block_tables, kv_lens,
                            q_lens, layer, table_base=None, *, scale,
                            interpret, stats, window=None, positions=None):
    """The kernel's launch for queries ``(B, Q, H, Dh)`` over pages of
    ``p`` heads a row (the lane juggling is in here, so that it too is
    traced once a program)."""
    return _over_packed_rows(
        functools.partial(_launch, pages_k=pages_k, pages_v=pages_v,
                          block_tables=block_tables, kv_lens=kv_lens,
                          q_lens=q_lens, layer=layer, scale=scale,
                          interpret=interpret, stats=stats, window=window,
                          table_base=table_base, positions=positions),
        q, pages_k, stats)


def window_table_pages(window, bs):
    """Entries of a window layer's table: the pages a row holds at most,
    the window's own and two more (the page its lower bound straddles and the
    one being written: room for ``bs + 2`` new positions before the pages
    behind the window go back to the pool). The pool, the scheduler's grants
    and the model's split of a packed table all ask here."""
    if window % bs:
        raise ValueError(f"a sliding window of {window} is whole pages of "
                         f"{bs} positions")
    return window // bs + 2


def group_segments(width, n_full, n_win, win_pages):
    """Where each layer's segment starts in a packed step table of two page
    groups, ``width`` entries wide: ``n_full`` global segments of ``full_pages``
    entries, ``n_win`` window segments of ``win_pages``, then the window
    tables' base in the last entry. Returns (full_pages, global starts,
    window starts): ``step_build._fill_row`` packs by it, the model's
    ``_paged_layers`` splits by it, ``PagedKVPool.check_step_writes`` reads
    by it."""
    full_pages = (width - 1 - n_win * win_pages) // n_full
    return (full_pages, [j * full_pages for j in range(n_full)],
            [n_full * full_pages + j * win_pages for j in range(n_win)])


def window_walk(window, qw, bs, nb):
    """Table entries a launch walks for a row of ``qw`` queries under a
    ``window``: the pages that positions ``first query - window + 1 .. last
    query`` can lie in, no more than the table has."""
    return min(nb, (window + qw - 2) // bs + 2)


def _launch(q, *, pages_k, pages_v, block_tables, kv_lens, q_lens, layer,
            scale, interpret, stats, window=None, table_base=None,
            positions=None):
    quant = isinstance(pages_k, QuantPages)
    b, qw, h, dh = q.shape
    data = pages_k.data if quant else pages_k
    _, _, hkv, bs, _ = data.shape
    g = h // hkv
    walk = block_tables.shape[1] if window is None \
        else window_walk(window, qw, bs, block_tables.shape[1])
    pages, heads = fetch_group(bs=bs, dh=dh, hkv=hkv, qg=qw * g,
                               page_dtype=data.dtype, nb=walk,
                               **({"positions": positions} if positions
                                  else {}))
    # head-major query rows: (B, H_kv, Q*g, Dh), so a grid step's block is
    # (heads, Q*g, Dh), one 2-D tile a head (no in-kernel reshape)
    qg = _to_head_major(q, hkv)
    lens = kv_lens.astype(jnp.int32)
    qlens = q_lens.astype(jnp.int32)
    layer_arr = jnp.reshape(layer, (1,))
    shift = None
    if window is not None or table_base is not None:
        # the walk starts at the page of the first query's lower bound
        # (entry 0 of the table is page ``table_base``), and every position
        # the kernel sees counts from that page's first: to the body a
        # windowed row is a short row
        base = jnp.zeros_like(lens) if table_base is None \
            else table_base.astype(jnp.int32)
        first = jnp.zeros_like(lens) if window is None else jax.lax.div(
            jnp.maximum(lens - qlens - (window - 1), 0), bs)
        first = jnp.maximum(first, base)
        shift = first - base
        lens = jnp.maximum(lens - first * bs, 0)
    tables = _fetch_table(block_tables.astype(jnp.int32), lens, bs, pages,
                          shift, walk)
    nb = tables.shape[1]

    def kv_index(i):
        def index(bi, hi, j, tbl, ln, qln, ly):
            # -1 holes (the body masks them on the entry's sign) fetch page 0
            return (ly[0], jnp.maximum(tbl[bi, j * pages + i], 0), hi, 0, 0)
        return index

    def q_index(bi, hi, j, tbl, ln, qln, ly):
        return (bi, hi, 0, 0)

    in_specs = [pl.BlockSpec((None, heads, qw * g, dh), q_index)]
    operands = [qg]
    for i in range(pages):
        # the pool once per page slot, each with its own walk of the table
        spec = pl.BlockSpec((None, None, heads, bs, dh), kv_index(i))
        in_specs += [spec, spec]
        if quant:
            # the scale sidecars chase the SAME index maps as their pages,
            # so a clamped dead-page fetch elides both DMAs together
            sspec = pl.BlockSpec((None, None, heads, bs, 1), kv_index(i))
            in_specs += [sspec, sspec]
            operands += [pages_k.data, pages_v.data, pages_k.scale,
                         pages_v.scale]
        else:
            operands += [pages_k, pages_v]

    out_specs = pl.BlockSpec((None, heads, qw * g, dh), q_index)
    out_shape = jax.ShapeDtypeStruct((b, hkv, qw * g, dh), q.dtype)
    if stats:
        # per-row online-softmax state rides along as two extra outputs —
        # the sequence-parallel merge's inputs (ops.softmax_merge)
        stat_spec = pl.BlockSpec((None, heads, qw * g, 1), q_index)
        stat_shape = jax.ShapeDtypeStruct((b, hkv, qw * g, 1), jnp.float32)
        out_specs = (out_specs, stat_spec, stat_spec)
        out_shape = (out_shape, stat_shape, stat_shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv // heads, nb // pages),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((heads, qw * g, 1), jnp.float32),
            pltpu.VMEM((heads, qw * g, 1), jnp.float32),
            pltpu.VMEM((heads, qw * g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, bs=bs, g=g, qw=qw,
                          tq=query_tile(qw * g, q.dtype), pages=pages,
                          quant=quant, stats=stats, window=window),
        # the name the device profile shows; the variants are other kernels
        name="tnn_paged_attention" + ("_win" if window else "")
        + ("_int8" if quant else "") + ("_stats" if stats else ""),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # scratch carries only along the innermost (group) sweep
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lens, qlens, layer_arr, *operands)
    if stats:
        return tuple(_from_head_major(x, qw) for x in out)
    return _from_head_major(out, qw)


def _to_head_major(x, hkv):
    """(B, Q, H, D) -> (B, H_kv, Q*g, D), row t*g + i = token t, group
    member i. A pure reshape for the decode form (Q == 1)."""
    b, qw, h, d = x.shape
    g = h // hkv
    return x.reshape(b, qw, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, qw * g, d)


def _from_head_major(x, qw):
    """Inverse of ``_to_head_major``: (B, H_kv, Q*g, D) -> (B, Q, H, D)."""
    b, hkv, rows, d = x.shape
    g = rows // qw
    return x.reshape(b, hkv, qw, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, qw, hkv * g, d)


def _gather_pages(pages, block_tables, layer, b, hkv, t, dh):
    if isinstance(pages, QuantPages):
        x = pages.data[layer][block_tables]  # (B, nb, Hkv, bs, Dh) int8
        s = pages.scale[layer][block_tables]
        x = x.astype(jnp.float32) * s        # dequant AT the gather
        return x.transpose(0, 2, 1, 3, 4).reshape(b, hkv, t, dh)
    x = pages[layer][block_tables]           # (B, nb, Hkv, bs, Dh)
    return x.transpose(0, 2, 1, 3, 4).reshape(b, hkv, t, dh)


def _pages_shape(pages):
    return pages.data.shape if isinstance(pages, QuantPages) else pages.shape


def _live_positions(block_tables, kv_lens, t, bs):
    """(B, T) live mask: positions inside kv_lens whose table entry is a
    real page — NEGATIVE entries are dead holes (pages another SP shard
    owns) and mask out their whole block. Identity when no -1 is present."""
    live = jnp.arange(t)[None, :] < kv_lens[:, None]
    return live & jnp.repeat(block_tables >= 0, bs, axis=1)


def _paged_attention_xla(q, pages_k, pages_v, block_tables, kv_lens, layer,
                         scale, stats=False):
    """Single-token (decode) reference — the PR 2 math (dead -1 table
    entries additionally masked, a numeric no-op when none are present)."""
    b, h, dh = q.shape
    _, _, hkv, bs, _ = _pages_shape(pages_k)
    g = h // hkv
    t = block_tables.shape[1] * bs

    tbl = jnp.maximum(block_tables, 0)   # clamp -1 holes for the gather
    k = _gather_pages(pages_k, tbl, layer, b, hkv, t, dh)
    v = _gather_pages(pages_v, tbl, layer, b, hkv, t, dh)
    qg = q.reshape(b, hkv, g, dh)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    live = _live_positions(block_tables, kv_lens, t, bs)  # (B, T)
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    if stats:
        # unnormalized form, emitting the same (m, l) state as the kernel's
        # online softmax — the SP merge's inputs
        m = jnp.max(s, axis=-1, keepdims=True)            # (B, Hkv, G, 1)
        p = jnp.where(live[:, None, None, :], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
        out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out / jnp.where(l == 0.0, 1.0, l)
        return (out.astype(q.dtype).reshape(b, h, dh),
                m.reshape(b, h, 1), l.reshape(b, h, 1))
    p = jax.nn.softmax(s, axis=-1)
    # rows with NO live position attend to NOTHING (output 0), matching the
    # kernel's l == 0 guard — softmax alone would return uniform garbage
    p = jnp.where(jnp.any(live, axis=-1)[:, None, None, None], p, 0.0)
    out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, h, dh)


def _paged_attention_xla_mq(q, pages_k, pages_v, block_tables, kv_lens,
                            q_lens, layer, scale, stats=False, window=None,
                            table_base=None):
    """Multi-token-query reference: same ragged causal mask as the kernel
    (and the same dead -1 table-entry masking; ``window`` and ``table_base``
    as ``paged_attention`` says).

    Works in the kernel's head-major row layout (B, H_kv, Q*g, ·): that
    keeps both contractions in the batched-matmul form of the decode
    reference — XLA's CPU backend has no bf16 x bf16 -> f32 dot for the
    token-major ``bqhgt,bhtd`` form."""
    b, qw, h, dh = q.shape
    _, _, hkv, bs, _ = _pages_shape(pages_k)
    g = h // hkv
    t = block_tables.shape[1] * bs

    tbl = jnp.maximum(block_tables, 0)   # clamp -1 holes for the gather
    k = _gather_pages(pages_k, tbl, layer, b, hkv, t, dh)
    v = _gather_pages(pages_v, tbl, layer, b, hkv, t, dh)
    s = jnp.einsum("bhrd,bhtd->bhrt", _to_head_major(q, hkv), k,
                   preferred_element_type=jnp.float32) * scale
    start = (kv_lens - q_lens)[:, None]                   # (B, 1)
    tpos = jnp.repeat(jnp.arange(qw), g)[None, :]         # (1, Q*g) token/row
    kpos = jnp.arange(t)[None, None, :]                   # absolute positions
    if table_base is not None:
        kpos = kpos + (table_base * bs)[:, None, None]
    live = (kpos <= (start + tpos)[:, :, None]) \
        & (tpos < q_lens[:, None])[:, :, None]            # (B, Q*g, T)
    if window is not None:
        live = live & (kpos > (start + tpos)[:, :, None] - window)
    live = live & jnp.repeat(block_tables >= 0, bs, axis=1)[:, None, :]
    s = jnp.where(live[:, None], s, _NEG_INF)
    if stats:
        m = jnp.max(s, axis=-1, keepdims=True)        # (B, Hkv, Q*g, 1)
        p = jnp.where(live[:, None], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
        out = jnp.einsum("bhrt,bhtd->bhrd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out / jnp.where(l == 0.0, 1.0, l)
        return (_from_head_major(out.astype(q.dtype), qw),
                _from_head_major(m, qw), _from_head_major(l, qw))
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked query rows (padding past q_lens, or q_lens/kv_lens == 0)
    # output exactly 0, matching the kernel's l == 0 guard
    row_live = (tpos < q_lens[:, None]) & (start + tpos >= 0)   # (B, Q*g)
    row_live = row_live & jnp.any(live, axis=-1)
    p = jnp.where(row_live[:, None, :, None], p, 0.0)
    out = jnp.einsum("bhrt,bhtd->bhrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return _from_head_major(out.astype(q.dtype), qw)


def paged_attention_reference(q, pages_k, pages_v, block_tables, kv_lens, *,
                              q_lens=None, layer=0,
                              scale: Optional[float] = None, window=None,
                              table_base=None):
    """XLA-lax reference: gather the tables contiguous, masked softmax.

    Same signature/semantics as ``paged_attention`` — the parity oracle for
    the kernel and the off-TPU fallback (it IS a gather, which is exactly
    what the kernel exists to avoid on TPU)."""
    return paged_attention(q, pages_k, pages_v, block_tables, kv_lens,
                           q_lens=q_lens, layer=layer, scale=scale,
                           backend="xla", window=window,
                           table_base=table_base)


def _check_args(q, pages_k, pages_v, block_tables, kv_lens, q_lens, scale):
    if isinstance(pages_k, QuantPages) != isinstance(pages_v, QuantPages):
        raise ValueError("pages_k / pages_v must both be QuantPages or "
                         "both plain arrays")
    if isinstance(pages_k, QuantPages):
        if pages_k.data.ndim == 4:   # single-layer: add the unit layer axis
            pages_k = QuantPages(pages_k.data[None], pages_k.scale[None])
            pages_v = QuantPages(pages_v.data[None], pages_v.scale[None])
        pk, pv = pages_k.data, pages_v.data
        for p, s in ((pages_k.data, pages_k.scale),
                     (pages_v.data, pages_v.scale)):
            if s.shape != p.shape[:-1] + (1,):
                raise ValueError(f"QuantPages scale {s.shape} must be pages "
                                 f"{p.shape} with the last axis collapsed "
                                 "to 1")
    else:
        if pages_k.ndim == 4:  # single-layer pages: add the unit layer axis
            pages_k, pages_v = pages_k[None], pages_v[None]
        pk, pv = pages_k, pages_v
    if pk.shape != pv.shape or pk.ndim != 5:
        raise ValueError(f"pages must both be (L, N, H_kv / p, bs, p * Dh); "
                         f"got {pk.shape} / {pv.shape}")
    was_3d = q.ndim == 3
    if was_3d:
        if q_lens is not None:
            raise ValueError("q_lens requires multi-token q (B, Q, H, Dh); "
                             f"got q {q.shape}")
        q = q[:, None]
    if q.ndim != 4:
        raise ValueError(f"q must be (B, H, Dh) or (B, Q, H, Dh); "
                         f"got {q.shape}")
    b, qw, h, dh = q.shape
    pack = max(pk.shape[4] // dh, 1)    # heads side by side in a page row
    hkv = pk.shape[2] * pack
    if h % hkv or pk.shape[4] != pack * dh:
        raise ValueError(f"q has {h} heads / Dh {dh} but pages carry "
                         f"{pk.shape[2]} rows of {pk.shape[4]} a position "
                         f"({hkv} kv heads); need rows of p * Dh and "
                         "H % H_kv == 0")
    if block_tables.shape[0] != b or kv_lens.shape != (b,):
        raise ValueError(f"block_tables {block_tables.shape} / kv_lens "
                         f"{kv_lens.shape} do not match batch {b}")
    if q_lens is None:
        q_lens = jnp.full((b,), qw, jnp.int32)
    elif q_lens.shape != (b,):
        raise ValueError(f"q_lens {q_lens.shape} does not match batch {b}")
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    return q, was_3d, q_lens, pages_k, pages_v, scale


@jax.named_scope("paged_attn")
def paged_attention(q, pages_k, pages_v, block_tables, kv_lens, *,
                    q_lens=None, layer=0, scale: Optional[float] = None,
                    backend: str = "auto",
                    interpret: Optional[bool] = None,
                    return_stats: bool = False,
                    window: Optional[int] = None, table_base=None,
                    group_positions: Optional[int] = None):
    """Ragged attention for the current step's query rows over paged KV.

    q : (B, H, Dh) — decode form, one token per sequence — or (B, Q, H, Dh)
        for ragged multi-token chunks (``q_lens[b]`` live tokens per row,
        left-aligned; the rest is padding and outputs exactly 0).
    pages_k / pages_v : (L, N, H_kv / p, bs, p * Dh) pool pages (or a single
        layer's 4-D slice; ``layer`` then ignored). Never copied: the kernel
        fetches only the pages the tables name. ``p`` (``lane_pack``) is
        read off the last dims: with ``p`` > 1 each query head is laid into
        the lanes of its KV head's slot of a row (zeros elsewhere), the same
        launch runs over ``H_kv / p`` heads of ``p * Dh`` with a group of
        ``p * g``, and each head keeps its own lanes of the output. ``scale``
        comes from the true ``Dh``; at ``p`` = 1 nothing is added.
    block_tables : (B, nb) int32 — page ids in logical order; entries past a
        row's live pages may be anything in-range (the pool pads with its
        scratch page 0).
    kv_lens : (B,) int32 — live KV positions per row INCLUDING the rows
        written this step (the engine scatters the new rows first and passes
        ``offsets + q_lens``). A 0 row outputs exactly 0.
    q_lens : (B,) int32 — live query tokens per row (only with 4-D q;
        defaults to the full width Q). Token t of row b sits at absolute
        position ``kv_lens[b] - q_lens[b] + t`` and attends causally.
    layer : which layer's pages to read (static or traced scalar).
    backend : "pallas" (the kernel; interprets off-TPU), "xla" (the gather
        reference), or "auto" — kernel on TPU, reference elsewhere (the
        reference is faster than interpret mode and numerically identical
        up to reduction order).

    GQA: H % H_kv == 0; each kv head's page is fetched once and attended by
    its whole query-head group. Returns q's shape.

    window : a SLIDING window (static): each query attends its own position
        and the ``window - 1`` before it. Pages wholly before the row's first
        query's lower bound are neither fetched nor walked (the grid is as
        long as a window's pages, not as the table), the page that straddles
        a bound is masked. The kernel is then named ``tnn_paged_attention_win``.
    table_base : (B,) int32 — the logical page that entry 0 of each row's
        table holds (default 0): a windowed row's table lists only the pages
        it still has, the ones behind its window given back to the pool.
        ``kv_lens`` stay absolute positions.
    group_positions : key positions a grid step covers (``fetch_group``'s
        ``positions``; default about 128).

    Block-table entries may be NEGATIVE: a -1 marks a dead hole (a page
    another sequence-parallel shard owns) whose positions are skipped as if
    masked. With ``return_stats`` the per-row online-softmax state rides
    along — returns ``(out, m, l)`` with m/l shaped like out with the head
    dim collapsed to 1 — which is exactly what
    ``ops.softmax_merge.merge_psum`` needs to combine shard partials into
    the full-row softmax.
    """
    q, was_3d, q_lens, pages_k, pages_v, scale = _check_args(
        q, pages_k, pages_v, block_tables, kv_lens, q_lens, scale)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown paged-attention backend {backend!r}")
    if backend == "pallas":
        if interpret is None:
            interpret = interpret_default()
        out = _paged_attention_pallas(
            q, pages_k, pages_v, block_tables, kv_lens, q_lens,
            jnp.asarray(layer, jnp.int32), table_base, scale=scale,
            interpret=interpret, stats=return_stats, window=window,
            positions=group_positions)
        if was_3d:
            out = jax.tree_util.tree_map(lambda x: x[:, 0], out)
        return out
    kw = dict(pages_k=pages_k, pages_v=pages_v, block_tables=block_tables,
              kv_lens=kv_lens, layer=layer, scale=scale, stats=return_stats)
    bounded = window is not None or table_base is not None
    if was_3d and not bounded:
        attend, q = functools.partial(_paged_attention_xla, **kw), q[:, 0]
    else:
        attend = functools.partial(_paged_attention_xla_mq, q_lens=q_lens,
                                   window=window, table_base=table_base,
                                   **kw)
    out = _over_packed_rows(attend, q, pages_k, return_stats)
    if was_3d and bounded:
        out = jax.tree_util.tree_map(lambda x: x[:, 0], out)
    return out


def scatter_kv_rows(pages, block_tables, offsets, rows, *, layer=None):
    """Write one new KV row per sequence at its decode position.

    The write half of the page contract: ``pages`` is (L, N, H / p, bs,
    p * Dh) with ``layer`` naming the layer (or a single layer's 4-D slice);
    ``block_tables`` (B, nb); ``offsets`` (B,) the position each row writes;
    ``rows`` (B, H, Dh). Rows whose table points at the pool's scratch page
    land there harmlessly. Returns the updated pages. The one-token case of
    ``scatter_kv_chunk``, which says how the write is made (and opens the
    ``kv_write`` scope: one level in an op's path, whichever form is called).
    """
    return scatter_kv_chunk(pages, block_tables, offsets, rows[:, None],
                            jnp.ones_like(offsets), layer=layer)


@jax.named_scope("kv_write")
def scatter_kv_chunk(pages, block_tables, starts, rows, q_lens, *,
                     layer=None):
    """Write a ragged chunk of new KV rows per sequence.

    ``rows`` is (B, Q, H, Dh) (regrouped to the pages' (H / p, p * Dh):
    adjacent heads, no data moves): row b's tokens t < q_lens[b] land at
    positions ``starts[b] + t`` through its block table; padding tokens (and
    whole rows with q_lens == 0) are redirected to the pool's scratch page
    0, which is never allocated to a request, so they can't corrupt live KV.
    Same layer semantics as ``scatter_kv_rows``.

    The write moves the row's SUBLANE TILES, in place: Q consecutive
    positions touch at most ``(Q + t - 2) // t + 1`` tiles of ``t`` page
    rows (``write_tile``: one register's sublanes, 16 rows of bf16; ONE tile
    for a decode row), and ``tnn_kv_row_write`` takes each LIVE one through a
    DMA into VMEM, found by the scalar-prefetched table entry and tile
    index, selects the new rows in and sends it back, the pool aliased in
    and out (``_write_rows_pallas``): nothing else of the pool moves, a dead
    tile costs a scalar test, and the write runs in the pool's own layout,
    the one the paged kernel reads. Off the chip, and for pages whose rows
    do not fill the lanes or are no whole tiles (``_kernel_writes``: an
    int8 pool's scale sidecar, GPT-2's unpacked int8 heads of 64), the
    write moves WHOLE PAGES instead, plain XLA (``_write_rows_xla``): each
    page a chunk can touch is gathered, the new rows selected into their
    slots, and the page scattered back, on the pool's LEADING dims only
    ([layer, blk]) so that it too runs in the pool's layout and, donated
    through jit, in place. (Indices on the slot axis, ``.at[layer, blk, :,
    slot]``, made the TPU scatter want another layout, and the compiler
    re-laid the WHOLE pool out and back around every layer's write.)

    Both are exact under the ONE-WRITER invariant the engine keeps
    (``PagedKVPool.check_step_writes``): a step writes a non-scratch page
    from one row only — a shared prefix page is cloned before its first
    write. What holds no live token, and -1 table holes (positions another
    SP shard owns), the kernel leaves out; the page form diverts those
    pages to the scratch page, where rows may overwrite each other: nothing
    reads it.

    QuantPages: rows are quantized HERE (write time) and the int8 data and
    f32 scale scatter through the same block-table math, so a row's scale
    can never drift from its page slot.
    """
    return write_rows(pages, block_tables, starts, rows, q_lens, layer=layer)


def write_tile(bs, page_dtype):
    """Rows of a page the row write moves at once: one register's sublanes
    (``_sublanes``: 16 rows of bf16, 8 of float32), the least a block of
    the page may hold; the whole page where its ``bs`` rows are no whole
    tiles. From the page's shape and dtype alone."""
    t = _sublanes(page_dtype)
    return t if bs % t == 0 else bs


def row_tiles(qw, t):
    """Tiles of ``t`` rows that ``qw`` consecutive positions can touch: one
    for a decode row, ``(qw + t - 2) // t + 1`` for a chunk that may start
    in mid-tile."""
    return (qw + t - 2) // t + 1


# what the row write's ring of tiles and its new rows (a decode step's whole,
# a chunk's a ring of their own) may take of VMEM, and the most tiles it keeps
# in flight (a DMA semaphore a transfer, three kinds of
# transfer a slot: a kernel has some 450 semaphores, and a transfer's
# latency is hidden long before that)
_WRITE_VMEM = 6 * 2 ** 20
_WRITE_SLOTS = 32


def _row_write_kernel(layer_ref, meta_ref, new_ref, pool_ref, out_ref, buf,
                      *rest, bn, n):
    """Every live tile of the step: the tile (and a chunk's new rows for it)
    comes into a slot of the ring, the rows ``[lo, hi)`` of the tile take
    the new values, and the tile goes back where it came from. ``meta``:
    ``bn`` table entries, tile indices, ``lo`` and ``hi``, tile ``i`` = row
    ``i // n``'s ``i % n``-th (the same numbers for every layer and for K
    and V of a step, so a program computes them once). A decode step's new rows
    (one a row) are whole in VMEM; a chunk's (and a batch's too wide for
    that) stay in HBM, laid out by tile, and come a tile at a time into
    ``nbuf``. ``pool_ref`` and ``out_ref`` are one buffer."""
    *nbuf, sem, live_ref = rest
    slots, _, t, _ = buf.shape          # the ring: ``slots`` tiles of ``t`` rows

    def of(i, field):
        """Tile ``i``'s table entry (0), tile index (1), ``lo``, ``hi``."""
        return meta_ref[field * bn + i]

    def mark(i, c):
        live = of(i, 3) > of(i, 2)

        @pl.when(live)
        def _keep():
            live_ref[c] = i
        return c + live.astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, bn, mark, 0)

    def tile(ref, c):
        """Live tile ``c``'s place in the pool."""
        i = live_ref[c]
        row0 = pl.multiple_of(of(i, 1) * t, t)
        at = (of(i, 0), slice(None), pl.ds(row0, t), slice(None))
        return ref.at[(layer_ref[0],) + at if len(ref.shape) == 5 else at]

    def tile_in(c):
        return pltpu.make_async_copy(tile(pool_ref, c), buf.at[c % slots],
                                     sem.at[0, c % slots])

    def tile_out(c):
        return pltpu.make_async_copy(buf.at[c % slots], tile(out_ref, c),
                                     sem.at[1, c % slots])

    def new_in(c):
        i = live_ref[c]
        rows = pl.ds(pl.multiple_of((i % n) * t, t), t)
        return pltpu.make_async_copy(new_ref.at[i // n, :, rows, :],
                                     nbuf[0].at[c % slots],
                                     sem.at[2, c % slots])

    def fetch(c, _):
        tile_in(c).start()
        if nbuf:
            new_in(c).start()

    def stored(c, _):
        tile_out(c).wait()

    jax.lax.fori_loop(0, jnp.minimum(n_live, slots), fetch, None)
    r = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 1)

    def one(c, _):
        i, slot = live_ref[c], c % slots
        here = (r >= of(i, 2)) & (r < of(i, 3))
        tile_in(c).wait()
        if nbuf:
            new_in(c).wait()
            fresh = nbuf[0][slot]
        else:           # the row's ONE new row, laid over the tile
            fresh = new_ref[i // n]
        buf[slot] = jnp.where(here, fresh, buf[slot])
        tile_out(c).start()

        # the slot of the tile before takes the tile a ring further on
        @pl.when((c >= 1) & (c - 1 + slots < n_live))
        def _refill():
            stored(c - 1, None)
            fetch(c - 1 + slots, None)

    jax.lax.fori_loop(0, n_live, one, None)
    jax.lax.fori_loop(jnp.maximum(n_live - slots, 0), n_live, stored, None)


# jitted: a program writes K and V of every layer through ONE traced and
# lowered function (tracing the kernel and lowering it for Mosaic cost 90 ms
# a call site, 6.5 s a 36-layer program, and a warm compile cache saves
# neither); XLA inlines the calls, each under its own site's scope
@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_rows_pallas(pages, block_tables, starts, rows, q_lens, layer,
                       interpret):
    """The row write as ONE aliased ``pallas_call``. The pool stays in HBM;
    the kernel walks the step's LIVE tiles (the ``(H / p, t, p * Dh)``
    sublane tile of the page that holds a row's positions, found through
    the scalar-prefetched table entry and tile index), each through one DMA
    in, a select against the tile's live rows and one DMA back, as many in
    flight as the ring holds. Nothing else of the pool moves, and a dead
    tile costs a scalar test."""
    bs = pages.shape[-2]
    b, qw, hp, width = rows.shape
    t = write_tile(bs, pages.dtype)
    n = row_tiles(qw, t)
    nbt = block_tables.shape[1]
    g = (starts // t)[:, None] + jnp.arange(n)          # (B, n) tiles, global
    lo = jnp.clip(starts[:, None] - g * t, 0, t)
    hi = jnp.clip((starts + q_lens)[:, None] - g * t, 0, t)
    entry = g // (bs // t)
    blk = jnp.take_along_axis(block_tables, jnp.clip(entry, 0, nbt - 1),
                              axis=1)
    # dead tiles, -1 holes (a raw -1 would wrap to the LAST page) and entries
    # past the table are not walked at all
    ok = (hi > lo) & (entry >= 0) & (entry < nbt) & (blk >= 0)
    meta = jnp.concatenate(
        [jnp.where(ok, x, 0).reshape(-1)
         for x in (blk, g % (bs // t), lo, hi)]).astype(jnp.int32)
    bn = b * n
    # half the budget for the tiles' ring, half for the new rows. A decode
    # step's, one a row, sit whole in VMEM where they fit (counted at a
    # tile's sublanes a row, the most a row of ONE pads to); a chunk's, and
    # a wider batch's, are laid out by tile here, the gather the page form
    # makes too, and come by DMA into a ring beside their tiles
    slots = max(2, min(bn, _WRITE_SLOTS, _WRITE_VMEM // (
        2 * _tile_bytes(hp * t, width, pages.dtype))))
    whole = qw == 1 and b * hp * _tile_bytes(1, width, pages.dtype) \
        <= _WRITE_VMEM // 2
    if whole:
        new = rows.reshape(b, hp, 1, width)
    else:
        # the chunk token that lands in each row of each touched tile
        tok = ((starts // t) * t - starts)[:, None] + jnp.arange(n * t)
        new = jnp.take_along_axis(
            rows, jnp.clip(tok, 0, qw - 1)[:, :, None, None],
            axis=1).swapaxes(1, 2)                      # (B, H / p, n * t, W)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    ring = pltpu.VMEM((slots, hp, t, width), pages.dtype)
    return pl.pallas_call(
        functools.partial(_row_write_kernel, bn=bn, n=n),
        name="tnn_kv_row_write",        # what the device profile shows
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM) if whole
                      else hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[ring] * (1 if whole else 2) + [
                pltpu.SemaphoreType.DMA((3, slots)),
                pltpu.SMEM((bn,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # operands count the two prefetched arrays: the pool is 3
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(0 if layer is None else layer, (1,)).astype(jnp.int32),
      meta, new, pages)


def _kernel_writes(pages):
    """Whether the write into ``pages`` (ONE array) is ``tnn_kv_row_write``:
    on the TPU, for pages of whole registers, rows that fill the 128 lanes
    (Mosaic refuses a tile of half-filled ones: an odd count of heads of 64,
    a tensor-parallel shard's 3 of gpt2_small's 12; an int8 pool's scale
    sidecar is one lane wide) and ``bs`` a whole number of sublane tiles.
    Everything else, and every pool off the chip, keeps the whole-page
    form. From the platform and the array alone."""
    return jax.default_backend() == "tpu" and pages.shape[-1] % 128 == 0 \
        and pages.shape[-2] % _sublanes(pages.dtype) == 0


def write_rows(pages, block_tables, starts, rows, q_lens, *, layer=None):
    """``scatter_kv_chunk`` under the caller's scope: the write for rows
    that are not a step's K/V (EVA's chunk summaries). An int8 pool's data
    and scale each go by their own shape (``_kernel_writes``), each at its
    own tile height."""
    if isinstance(pages, QuantPages):
        return QuantPages(*(
            write_rows(p, block_tables, starts, r, q_lens, layer=layer)
            for p, r in zip(pages, quantize_kv_rows(rows))))
    if pages.ndim == 5 and layer is None:
        raise ValueError("layer is required for (L, N, H, bs, Dh) pages")
    # a packed page row holds ``pages.shape[-1] // Dh`` adjacent heads side
    # by side (``lane_pack``): the same bytes as the new rows, regrouped
    rows = rows.reshape(*rows.shape[:2], pages.shape[-3], pages.shape[-1])
    form = functools.partial(_write_rows_pallas, interpret=interpret_default()) \
        if _kernel_writes(pages) else _write_rows_xla
    return form(pages, block_tables, starts, rows, q_lens, layer)


def _write_rows_xla(pages, block_tables, starts, rows, q_lens, layer):
    """The write in WHOLE PAGES, plain XLA: every page a row's chunk can
    touch is gathered, the new rows selected into their slots, the page
    scattered back. Off the chip, for pages the kernel does not take
    (``_kernel_writes``), and what the kernel is tested against."""
    bs = pages.shape[-2]
    b, qw = rows.shape[:2]
    nbt = block_tables.shape[1]
    npg = row_tiles(qw, bs)
    entry = (starts // bs)[:, None] + jnp.arange(npg)     # (B, P) table slots
    # the chunk token that lands in each slot of each touched page
    tok = (entry * bs - starts[:, None])[:, :, None] + jnp.arange(bs)
    live = (tok >= 0) & (tok < q_lens[:, None, None])     # (B, P, bs)
    blk = jnp.take_along_axis(block_tables, jnp.clip(entry, 0, nbt - 1),
                              axis=1)
    # a raw -1 would wrap to the LAST page and corrupt live KV
    blk = jnp.where(live.any(-1) & (entry < nbt), jnp.maximum(blk, 0), 0)
    new = jnp.take_along_axis(
        rows, jnp.clip(tok, 0, qw - 1).reshape(b, npg * bs, 1, 1), axis=1)
    new = new.reshape(b * npg, bs, *rows.shape[2:]).swapaxes(1, 2)
    here = live.reshape(b * npg, 1, bs, 1)
    # the first read of a program's pool argument has no producer to take its
    # layout from; left free, the compiler re-lays the pool out once more for
    # that one gather
    pages = with_layout_constraint(
        pages, Layout(major_to_minor=tuple(range(pages.ndim))))
    at = (layer, blk.reshape(-1)) if pages.ndim == 5 else blk.reshape(-1)
    return pages.at[at].set(jnp.where(here, new, pages[at]))
