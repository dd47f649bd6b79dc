"""Blockwise fused attention (FlashAttention-2 style) as a Pallas TPU kernel.

The TPU-native replacement for the reference's flash-attention capability
(FlashAttentionBlock delegating to cuDNN-frontend fused SDPA,
src/nn/blocks_impl/flash_attention_block.cpp:74-338; an abandoned CPU blockwise kernel
at include/nn/blocks_impl/cpu/flash_attention.hpp:18-80 used Br=64/Bc=64 online softmax —
same algorithm, here actually working and TPU-tiled).

Forward: online-softmax accumulation over key blocks with O(block) VMEM, grid
(batch*heads, q_blocks, k_blocks), causal blocks fully above the diagonal skipped;
the per-row logsumexp L is written out for the backward.
Backward: blockwise Pallas kernels too (FlashAttention-2 style) — one pass
accumulating dQ over key blocks, one accumulating dK/dV over query blocks, both
O(block) memory, so long-context TRAINING never materializes the (S, S) logits
(the earlier XLA recompute backward OOMed at S=8k).

Inside a fetched block the kernels compute what the call's masks leave: where
the block's place is known while tracing (one block a head, no ``kv_offset``:
the training call up to S = 1,024) they walk SUB_TILE sub-tiles, leave out
those above the diagonal and build masks only for those a dead element is in;
elsewhere the whole block runs masked or unmasked under ``pl.when``.
``causal_tile_plan`` counts the sub-tiles of each kind.

Falls back to interpret mode off-TPU so the same code path tests on CPU.
"""
from __future__ import annotations

import collections
import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

# Tuned on one TPU v5e chip, 2026-10-02 (PR 45; host clock round 8 chained
# calls, the best of 4 x 3), at gpt2-medium.train's call: B 8, H 16, S 1,024,
# Dh 64, bf16, causal; ms a call, forward / backward (fwd+bwd less fwd):
#   before: one (1024, 1024) tile, every mask on it; fused bwd at 512 x 512
#                                                          0.657 / 1.03
#   a grid of 512 (256) blocks, one tile a block           0.82 (1.49) / -
#   sub-tiles 256 walked one by one, state in values        0.78 / 0.99
#     ... the same as a lax.fori_loop                      1.26 / 1.83
#   one block, one tile, masks built once, scale on q      0.44 / 1.09
#   sub-tiles 512 / 256 / 128, matmuls over the long side  0.37 / 0.33 / 0.32
#                                                  bwd     0.89 / 0.76 / 0.77
# What decided it: (1) a matmul wants its LONG side streamed (a (256, 64) x
# (64, 1024) product reloads the MXU's stationary operand for 256 rows of
# work), so scores, P V, dP and dQ run a key sub-tile at a time over all the
# query rows below it, dV and dK a query sub-tile at a time over all its
# keys, with the (256, 256) tiles of S, P and dS held in between; (2) the
# softmax's row statistics are (rows, 1) columns, as dear to the vector unit
# as a (rows, 128) tile, and every reduction crosses the lanes once: the
# online update runs once a query sub-tile, over all its tiles folded
# together; (3) where one block is the whole sequence nothing is carried
# between grid steps, so the forward keeps no scratch. 2048-wide blocks fail
# to compile (VMEM). S = 4,096 (B 2; a grid of 1024 blocks, whole-block
# tiles, masks on the diagonal blocks only): 1.67 -> 1.37 / 3.02 -> 2.78.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
DEFAULT_BLOCK_Q_BWD = 512
DEFAULT_BLOCK_K_BWD = 1024
DEFAULT_BLOCK_FUSED_BWD = 1024
# The sub-tile a kernel body walks inside the (bq, bk) block a grid step has
# fetched, where the block's place is known while tracing (a side it does
# not divide is one sub-tile).
SUB_TILE = 256
_NEG_INF = -1e30


# ---- which sub-tiles of a fetched block a kernel visits -------------------
# Inside a block, query row r (counted from the block's first row) may attend
# key column j (from the block's first column) iff j <= r + delta (causal;
# delta = q_start + kv_offset - k_start) and j < kvl (kvl = kv_len - k_start:
# the keys past it are padding). Both are Python ints when the call states
# them (a grid of one block, no kv_offset): the block is then walked in
# sub-tiles whose spans are ints, and the dead ones are left out while
# tracing. Otherwise the sub-tile is the block, and whether it runs, and with
# or without masks, is a ``pl.when`` on two traced comparisons.

def _sub_tiles(bq: int, bk: int, sub_q: int, sub_k: int, static: bool):
    """The (tq, tk) sub-tile of a (bq, bk) block: the block itself where its
    place is traced, or on the side that the sub-tile does not divide."""
    if not static:
        return bq, bk
    return (sub_q if bq % sub_q == 0 else bq), (sub_k if bk % sub_k == 0
                                                  else bk)


def _query_span(c: int, delta, kvl: int, tq: int, tk: int, na: int):
    """Key sub-tile ``c`` of a block -> ``(a_first, a_full)``: of the block's
    ``na`` query sub-tiles, [0, a_first) hold no live element (not visited),
    [a_first, a_full) a dead one (masked), [a_full, na) none (unmasked)."""
    inside = min(max(kvl - c * tk, 0), tk)       # keys that are no padding
    a_first = a_full = 0
    if delta is not None:
        x = c * tk - delta          # the first row that sees this sub-tile
        a_first = min(max(x, 0) // tq, na)
        a_full = min(-(-max(x + tk - 1, 0) // tq), na)
    return (na if inside == 0 else a_first), (na if inside < tk else a_full)


TilePlan = collections.namedtuple("TilePlan", "unmasked masked skipped")


def causal_tile_plan(sq: int, skv: int, block_q: int, block_k: int,
                     sub_q: int, sub_k: int, causal: bool = True) -> TilePlan:
    """What one head costs in sub-tiles: how many the kernels visit without
    masks, how many with, and how many they leave out, for a call without
    ``kv_offset`` and without an explicit mask. Summed from the same
    ``_sub_tiles`` and ``_query_span`` the kernels are built from."""
    bq, bk, sq_p, skv_p = _block_geometry(sq, skv, block_q, block_k)
    tq, tk = _sub_tiles(bq, bk, sub_q, sub_k, (sq_p, skv_p) == (bq, bk))
    na, nc = bq // tq, bk // tk
    unmasked = masked = 0
    for q_start in range(0, sq_p, bq):
        for k_start in range(0, skv_p, bk):
            for c in range(nc):
                a_first, a_full = _query_span(
                    c, q_start - k_start if causal else None, skv - k_start,
                    tq, tk, na)
                unmasked += na - a_full
                masked += a_full - a_first
    total = (sq_p // tq) * (skv_p // tk)
    return TilePlan(unmasked, masked, total - unmasked - masked)


def _one_static_block(nq: int, nk: int, static_off: bool) -> bool:
    return nq == 1 and nk == 1 and static_off


def _static(*xs) -> bool:
    return all(isinstance(x, int) for x in xs)


def _rows(start, rows):
    """The block's rows ``[rows[0], rows[1])`` in a ref that holds the whole
    sequence, where the block begins at row ``start``."""
    if _static(start):
        return slice(start + rows[0], start + rows[1])
    return pl.ds(start + rows[0], rows[1] - rows[0])


class _Geometry:
    """Where a grid step's block sits: its sub-tiles, their spans and their
    masks. One per kernel invocation."""

    def __init__(self, off_ref, mask_ref, qi, ki, *, causal, bq, bk, sub,
                 kv_len, static_off, nq, nk):
        self.mask_ref, self.bq, self.bk = mask_ref, bq, bk
        # a grid of one block without an offset: where the diagonal and the
        # padding cross the block is known while tracing, and the block is
        # walked in sub-tiles; else the sub-tile is the block
        self.static = _one_static_block(nq, nk, static_off)
        self.tq, self.tk = _sub_tiles(bq, bk, sub, sub, self.static)
        self.na, self.nc = bq // self.tq, bk // self.tk
        self.q_start = 0 if nq == 1 else qi * bq
        k_start = 0 if nk == 1 else ki * bk
        off = 0 if static_off else off_ref[0]
        self.delta = self.q_start + off - k_start if causal else None
        self.kvl = kv_len - k_start
        # a row may have NO live key only under an explicit mask or an
        # offset the kernel cannot see: with neither, key 0 is live for all
        self.rows_may_be_dead = mask_ref is not None or (
            causal and not static_off)

    def walk(self, body) -> None:
        """``body(columns)`` with ``columns`` a list of ``(c, first, until)``,
        one a key sub-tile ``c`` that some query sub-tile sees: the query
        sub-tiles ``[first, na)`` see it, of which ``[first, until)`` hold a
        dead element and run masked. A static block: traced once, the
        columns from ``_query_span``. Else the block is the one sub-tile of
        either side: once unmasked and once masked, under ``pl.when``."""
        explicit = self.mask_ref is not None
        if self.static:
            columns = []
            for c in range(self.nc):
                first, until = _query_span(c, self.delta, self.kvl, self.tq,
                                           self.tk, self.na)
                if first < self.na:
                    columns.append((c, first, self.na if explicit else until))
            body(columns)
            return
        live, full = True, not explicit
        if full:
            full = self.kvl >= self.bk                     # no padding in it
        if self.delta is not None:
            # a key block strictly above the diagonal contributes nothing;
            # one wholly below it needs no mask
            live = self.delta + self.bq - 1 >= 0
            full = jnp.logical_and(full, self.delta >= self.bk - 1)
        pl.when(full)(lambda: body([(0, 0, 0)]))
        pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
            lambda: body([(0, 0, 1)]))

    def live_mask(self, rows, cols):
        """Bool (rows, cols) of the live elements of the block's rows
        ``[rows[0], rows[1])`` x columns ``[cols[0], cols[1])``; the bounds
        known not to bite there are left out."""
        shape = (rows[1] - rows[0], cols[1] - cols[0])
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        terms = []
        pad = self.kvl - cols[0]                   # keys before the padding
        if not (_static(pad) and pad >= shape[1]):
            terms.append(col < pad)
        if self.delta is not None:
            diag = rows[0] + self.delta - cols[0]  # col - row <= diag: live
            if not (_static(diag) and diag >= shape[1] - 1):
                row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                terms.append(col - row <= diag)
        if self.mask_ref is not None:
            terms.append(
                self.mask_ref[0, slice(*rows), slice(*cols)] != 0)
        return functools.reduce(jnp.logical_and, terms)


def _scaled(x, scale: float):
    """``x * scale`` in x's dtype, rounded once (exact for a power of two,
    which 1 / sqrt(64) is): the (rows, d) operand carries the softmax scale
    so that no (rows, keys) tile is multiplied by it."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _row_reduce(fold, reduce, tiles):
    """``reduce(..., axis=1, keepdims=True)`` over the tiles side by side:
    their 128-lane column blocks are folded together on the vector unit
    first, so that ONE reduction crosses the lanes however many tiles a row
    spans."""
    if any(t.shape[1] % 128 for t in tiles):
        return functools.reduce(
            fold, [reduce(t, axis=1, keepdims=True) for t in tiles])
    blocks = [t[:, i:i + 128] for t in tiles
              for i in range(0, t.shape[1], 128)]
    return reduce(functools.reduce(fold, blocks), axis=1, keepdims=True)


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                has_mask: bool, nk: int, **geometry):
    mask_ref, rest = (rest[0], rest[1:]) if has_mask else (None, rest)
    o_ref, lse_ref, *scratch = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    g = _Geometry(off_ref, mask_ref, qi, ki, nk=nk, **geometry)
    # a grid of one block whose spans are known carries nothing from step to
    # step: no scratch, the rows' results are written as they are made
    carried = bool(scratch)
    if carried:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)  # (bq, 1)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def finish(r, m, l, acc):
        lsafe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, r, :] = (acc / lsafe).astype(o_ref.dtype)
        # logsumexp per row for the backward; +inf on fully-masked/padded rows
        # makes their p = exp(s - L) exactly 0 there (never NaN).
        # compact (bq, 1) column — 4 bytes/row in HBM end to end, vs the
        # lane-replicated 128-lane layout that cost ~400MB transient f32 at
        # B=8/H=12/S=8k (Mosaic pads narrow minor dims in VMEM transparently)
        lse_ref[0, r, :] = jnp.where(l > 0.0, m + jnp.log(lsafe), jnp.inf)

    def softmax(r, tiles):
        """The online-softmax update of rows ``r`` over score tiles
        ``[(s, live or None)]`` -> ``(m, l, alpha or None, [p])``. The row
        statistics are (rows, 1) columns, as dear an operand as a (rows, 128)
        tile: they are touched once for all the tiles."""
        m = _row_reduce(jnp.maximum, jnp.max, [s for s, _ in tiles])
        alpha = None
        if carried:
            m_prev = m_scr[r, :]
            m = jnp.maximum(m_prev, m)
            alpha = jnp.exp(m_prev - m)
        ps = []
        for s_t, live in tiles:
            p = jnp.exp(s_t - m)
            if live is not None and g.rows_may_be_dead:
                # rows with NO live key so far have m == _NEG_INF, which
                # would give the masked entries exp(0) = 1; zero them so
                # fully masked rows end with l == 0 (-> output 0, lse +inf)
                p = jnp.where(live, p, 0.0)
            ps.append(p)
        l = _row_reduce(jnp.add, jnp.sum, ps)
        if carried:
            l = l + alpha * l_scr[r, :]
        return m, l, alpha, ps

    def settle(r, m, l, alpha, acc):
        if carried:
            m_scr[r, :], l_scr[r, :] = m, l
            acc_scr[r, :] = alpha * acc_scr[r, :] + acc
        else:
            finish(r, m, l, acc)

    def block(columns):
        """Every matmul streams its LONG side through the MXU: the scores
        and P V a key sub-tile at a time over all the query rows that see
        it, the softmax a query sub-tile at a time over the (tq, tk) score
        tiles held in between."""
        tq, tk = g.tq, g.tk
        qs = _scaled(q_ref[0], scale)
        tiles = {}                        # (a, c) -> (s, live or None), then p
        for c, first, until in columns:
            cols = (c * tk, (c + 1) * tk)
            s_c = _dot(qs[first * tq:], k_ref[0, slice(*cols), :], _NT)
            for a in range(first, g.na):
                s_t, live = s_c[(a - first) * tq:(a - first + 1) * tq], None
                if a < until:
                    live = g.live_mask((a * tq, (a + 1) * tq), cols)
                    s_t = jnp.where(live, s_t, _NEG_INF)
                tiles[a, c] = s_t, live
        stats = {}
        for a in range(g.na):
            cs = [c for c, first, _ in columns if first <= a]
            r = slice(a * tq, (a + 1) * tq)
            if cs:
                *stats[a], ps = softmax(r, [tiles[a, c] for c in cs])
                for c, p in zip(cs, ps):
                    tiles[a, c] = p.astype(v_ref.dtype)
            elif not carried:       # no key of the block is live for them
                finish(r, *(jnp.zeros((tq, n), jnp.float32)
                            for n in (1, 1, qs.shape[1])))
        acc = dict.fromkeys(stats, 0.0)
        for c, first, _ in columns:
            o = _dot(jnp.concatenate([tiles[a, c]
                                      for a in range(first, g.na)]),
                     v_ref[0, c * tk:(c + 1) * tk, :], _NN)
            for a in range(first, g.na):
                acc[a] = acc[a] + o[(a - first) * tq:(a - first + 1) * tq]
        for a, (m, l, alpha) in stats.items():
            settle(slice(a * tq, (a + 1) * tq), m, l, alpha, acc[a])

    g.walk(block)

    if carried:
        @pl.when(ki == nk - 1)
        def _final():
            finish(slice(None), m_scr[:], l_scr[:], acc_scr[:])


def _block_geometry(sq: int, skv: int, block_q: int, block_k: int):
    """Block sizing + padded lengths. Forward and backward call this with
    their OWN block sizes — the lse residual is saved unpadded and the
    backward re-pads it (+inf) to its own geometry."""
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 8))
    return bq, bk, pl.cdiv(sq, bq) * bq, pl.cdiv(skv, bk) * bk


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _norm_mask(mask, b, h, sq, skv):
    """Normalize a boolean mask broadcastable to (B, H, Sq, Skv) into the
    kernel's grouped (G, Sq, Skv) int8 layout, G in {1, B, B*H} — the block
    index map selects the right group per (batch*head), so a (B, 1, Sq, Skv)
    padding mask is NOT materialized H times."""
    if mask.ndim == 2:
        mask = mask[None, None]
    elif mask.ndim == 3:
        # ambiguous: numpy broadcasting would align the leading axis with H,
        # but a (B, Sq, Skv) padding mask is the likelier intent — demand 4-D
        # so the two sdpa backends can never silently disagree
        if mask.shape[0] != 1:
            raise ValueError(
                f"3-D mask with leading dim {mask.shape[0]} is ambiguous "
                "(B or H?); pass a 4-D mask shaped (B, 1, Sq, Skv) or "
                "(1, H, Sq, Skv)")
        mask = mask[None]
    mb, mh = mask.shape[0], mask.shape[1]
    if (mb, mh) == (1, 1):
        g = mask.reshape(1, *mask.shape[2:])
    elif mh == 1:
        g = mask.reshape(mb, *mask.shape[2:])
    else:  # per-head masks: materialize (b*h) groups
        g = jnp.broadcast_to(mask, (b, h) + mask.shape[2:]).reshape(
            b * h, *mask.shape[2:])
    g = jnp.broadcast_to(g, (g.shape[0], sq, skv))
    return g.astype(jnp.int8)


def _mask_pick(groups: int, b: int, h: int):
    """Flattened batch*head grid index -> mask group index."""
    if groups == 1:
        return lambda bh: 0
    if groups == b:
        return lambda bh: bh // h
    return lambda bh: bh  # groups == b*h


def _mask_spec(mask, b, h, bq, bk, block_idx):
    """BlockSpec for the grouped (G, Sq, Skv) int8 mask. ``block_idx`` maps
    the kernel's grid indices -> (q block, k block), so each grid order (and
    any dead-block fetch clamping) plugs in its own mapping."""
    pick = _mask_pick(mask.shape[0], b, h)
    return pl.BlockSpec((1, bq, bk),
                        lambda *g: (pick(g[0]),) + tuple(block_idx(*g)),
                        memory_space=pltpu.VMEM)


_OFF_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, mask, off, causal, scale, block_q, block_k,
           block_q_bwd, block_k_bwd, clamp_dead):
    return _flash_fwd(q, k, v, mask, off, causal, scale, block_q, block_k,
                      clamp_dead=clamp_dead)[0]


def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    mask: Optional[jax.Array] = None,
                    kv_offset=None):
    """Fused attention over (B, H, S, Dh) tensors. Differentiable; O(block) fwd memory.

    Forward and backward take independent block geometry (see the tuning
    note above). ``block_*_bwd=None`` resolves, for the fused one-sweep
    backward, to its own default (DEFAULT_BLOCK_FUSED_BWD, or half of it
    where that does not fit), and for the split kernels to min(caller's fwd
    block, tuned bwd default): a caller shrinking blocks to fit VMEM shrinks
    the split backward too, while the stock defaults give it (512, 1024).

    ``mask``: boolean, broadcastable to (B, H, Sq, Skv); True = attend. Kept
    in its broadcast-group form ((B,1,..) padding masks are never tiled per
    head). ``kv_offset``: absolute position of q[0] in the kv sequence
    (cached decode with S_q != S_kv); may be a traced scalar. Both compose
    with ``causal``.

    When causal and kv_offset is statically absent (the self-attention
    training case), blocks strictly above the diagonal are not just
    compute-skipped but FETCH-skipped: their index maps clamp to the last
    live block, and the Pallas pipeline elides the DMA when a block index
    repeats — at S=8192 that removes ~40% of the K/V HBM traffic.

    Grouped-query attention: ``k``/``v`` may carry H_kv heads with
    H % H_kv == 0 (e.g. MQA at H_kv=1). The kernels never materialize the
    repeated heads — each q head's grid index maps to its kv head inside the
    BlockSpec index maps, so a shared kv block is fetched once and reused by
    the whole group (consecutive grid steps repeat the index; the pipeline
    elides the copy)."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    hkv = k.shape[1]
    if h % hkv or v.shape[1] != hkv:
        raise ValueError(f"q has {h} heads but k/v have {k.shape[1]}/"
                         f"{v.shape[1]}; need H % H_kv == 0 and k == v heads")
    if mask is not None:
        mask = _norm_mask(jnp.asarray(mask), b, h, sq, skv)
    clamp_dead = causal and kv_offset is None
    if kv_offset is None:
        off = jnp.zeros((1,), jnp.int32)
    else:
        off = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    return _flash(q, k, v, mask, off, causal, scale, block_q, block_k,
                  block_q_bwd, block_k_bwd, clamp_dead)


def _bwd_blocks(block_q, block_k, block_q_bwd, block_k_bwd):
    bq = block_q_bwd if block_q_bwd is not None else min(block_q, DEFAULT_BLOCK_Q_BWD)
    bk = block_k_bwd if block_k_bwd is not None else min(block_k, DEFAULT_BLOCK_K_BWD)
    return bq, bk


def _kv_head_map(h: int, hkv: int):
    """Flattened batch*q-head grid index -> flattened batch*kv-head index
    (identity when h == hkv); the zero-copy GQA mapping."""
    if h == hkv:
        return lambda bh: bh
    group = h // hkv
    return lambda bh: (bh // h) * hkv + (bh % h) // group


def _ambient():
    """What a launch reads from the process while it is traced (the
    environment, this module's constants): a static argument of the jits
    below, unused by them, so that their caches see a change of it."""
    return (interpret_default(), SUB_TILE, DEFAULT_BLOCK_FUSED_BWD,
            os.environ.get("TNN_FLASH_FUSED_BWD", "1"))


def _flash_fwd(q, k, v, mask, off, causal, scale, block_q, block_k,
               block_q_bwd=None, block_k_bwd=None, clamp_dead=False):
    return _flash_fwd_launch(_ambient(), q, k, v, mask, off, causal, scale,
                             block_q, block_k, clamp_dead)


# INLINED jits, here and round the backward: a step program calls them once a
# layer with the same shapes, so a kernel's body (unrolled over its sub-tiles)
# is traced once a program and not once a layer, which on a slow host was
# most of a training job's start (0.46 s a call, 48 calls), and the equal
# ``pallas_call`` equations are lowered once; every call keeps its own scope.
@functools.partial(jax.jit, inline=True, static_argnums=(0, 6, 7, 8, 9, 10))
def _flash_fwd_launch(ambient, q, k, v, mask, off, causal, scale, block_q,
                      block_k, clamp_dead):
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq, bk, sq_p, skv_p = _block_geometry(sq, skv, block_q, block_k)

    qf = _pad_to(q.reshape(b * h, sq, d), sq_p, 1)
    kf = _pad_to(k.reshape(b * hkv, skv, d), skv_p, 1)
    vf = _pad_to(v.reshape(b * hkv, skv, d), skv_p, 1)
    kv_head = _kv_head_map(h, hkv)

    grid = (b * h, sq_p // bq, skv_p // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        sub=SUB_TILE, kv_len=skv, has_mask=mask is not None,
        static_off=clamp_dead or not causal, nq=grid[1], nk=grid[2])
    if clamp_dead and causal:
        # causal + no kv_offset: a k block with ki > max_live is all-masked.
        # Clamping its fetch index to the row's last live block repeats the
        # previous step's index, so the pipeline elides the DMA entirely
        # (the kernel's pl.when(live) already skips the compute).
        def kv_idx(bh, qi, ki):
            return (kv_head(bh), jnp.minimum(ki, (qi * bq + bq - 1) // bk), 0)
    else:
        def kv_idx(bh, qi, ki):
            return (kv_head(bh), ki, 0)
    in_specs = [
        _OFF_SPEC,
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_idx, memory_space=pltpu.VMEM),
    ]
    inputs = [off, qf, kf, vf]
    if mask is not None:
        mp = _pad_to(_pad_to(mask, sq_p, 1), skv_p, 2)  # pad = masked out
        in_specs.append(_mask_spec(
            mp, b, h, bq, bk,
            lambda bh, qi, ki: (qi, kv_idx(bh, qi, ki)[1])))
        inputs.append(mp)
    out, lse = pl.pallas_call(
        kernel,
        name="tnn_flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        # nothing is carried where the kernel knows its one block's spans
        scratch_shapes=[] if _one_static_block(
            grid[1], grid[2], clamp_dead or not causal) else [
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
        # scratch carries only along the innermost (ki) sweep; bh and qi
        # iterations are independent, which lets Mosaic pipeline them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(),
    )(*inputs)
    out = out[:, :sq].reshape(b, h, sq, d)
    # residual is the compact UNPADDED (b*h, sq) row vector — the backward may
    # use different block geometry and re-pads with +inf itself
    return out, (q, k, v, mask, off, out, lse[:, :sq, 0])


def _bwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                scale, has_mask, outputs, nq, nk, **geometry):
    """The backward of one (q block, k block) pair, by what it ``outputs``:
    "dkv": dK and dV of one key block accumulated over the query blocks, grid
    (bh, k block j, q block i), 4 matmuls a visited tile. "fused": dQ in the
    SAME sweep, 5 matmuls a tile (the FlashAttention-2 ideal) vs 7 across the
    split kernels (S and dO@V^T were each computed twice): dQ accumulates in
    a FULL-SEQUENCE f32 VMEM scratch (sq x d = 2 MB at S=8192/D=64 — the
    cheap side; dK+dV would need twice that) and is written out once per bh.
    The TPU grid is sequential per core, which is what makes the whole-sweep
    scratch accumulation sound. "dq": the split path's other half, dQ of one
    query block over the key blocks, grid (bh, i, j), 3 matmuls a tile."""
    mask_ref, rest = (rest[0], rest[1:]) if has_mask else (None, rest)
    dq_ref = dq_scr = dk_ref = dk_scr = None
    if outputs == "dq":
        dq_ref, dq_scr, delta_scr = rest
        qi, ki = pl.program_id(1), pl.program_id(2)
        dq_first, dq_last, dq_start = ki == 0, ki == nk - 1, 0
    else:
        ki, qi = pl.program_id(1), pl.program_id(2)
        if outputs == "dkv":
            dk_ref, dv_ref, dk_scr, dv_scr, delta_scr = rest
        else:
            dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, delta_scr = rest
            dq_first = jnp.logical_and(ki == 0, qi == 0)
            dq_last = jnp.logical_and(ki == nk - 1, qi == nq - 1)
    g = _Geometry(off_ref, mask_ref, qi, ki, nq=nq, nk=nk, **geometry)
    if outputs == "fused":
        dq_start = g.q_start

    if dq_scr is not None:
        @pl.when(dq_first)
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    if dk_scr is not None:
        @pl.when(qi == 0)
        def _init_dkv():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    # delta_i = rowsum(dO_i * O_i), once a grid step (a dead block's, whose
    # fetch was elided, reads the block that is there: finite, and unused)
    delta_scr[:] = jnp.sum(do_ref[0].astype(jnp.float32)
                           * o_ref[0].astype(jnp.float32),
                           axis=1, keepdims=True)

    def block(columns):
        """Every matmul streams its LONG side through the MXU (a short
        stream reloads the stationary operand for little work): scores, dP
        and dQ a key sub-tile at a time over all the query rows that see it,
        dV and dK a query sub-tile at a time over all the keys it sees, with
        the (tq, tk) tiles of P and dS held between the two."""
        tq, tk = g.tq, g.tk
        tiles = {}                            # (a, c) -> (P, dS), as operands
        for c, first, until in columns:
            cols, rows = (c * tk, (c + 1) * tk), (first * tq, g.na * tq)
            r = slice(*rows)
            k, v = _scaled(k_ref[0, slice(*cols), :], scale), \
                v_ref[0, slice(*cols), :]
            q, do = q_ref[0, r, :], do_ref[0, r, :]
            s = _dot(q, k, _NT)                   # k carries the scale
            dp = _dot(do, v, _NT)
            for a in range(first, g.na):
                at = slice((a - first) * tq, (a - first + 1) * tq)
                ra = (a * tq, (a + 1) * tq)
                s_t = s[at]
                if a < until:
                    s_t = jnp.where(g.live_mask(ra, cols), s_t, _NEG_INF)
                # L = +inf on fully-masked/padded rows -> p = 0 there
                p = jnp.exp(s_t - lse_ref[0, slice(*ra), :])
                ds = p * (dp[at] - delta_scr[slice(*ra), :])
                tiles[a, c] = p.astype(do.dtype), ds.astype(q.dtype)
            if dq_scr is not None:
                dq_scr[_rows(dq_start, rows), :] += _dot(jnp.concatenate(
                    [tiles[a, c][1] for a in range(first, g.na)]), k, _NN)
        if dk_scr is None:
            return
        for a in range(g.na):
            seen = [tiles[a, c] for c, first, _ in columns if first <= a]
            if seen:
                # the keys a query sub-tile sees begin at the block's first
                r = slice(a * tq, (a + 1) * tq)
                cols = slice(0, len(seen) * tk)
                p, ds = (jnp.concatenate(x, axis=1) for x in zip(*seen))
                dv_scr[cols, :] += _dot(p, do_ref[0, r, :], _TN)
                dk_scr[cols, :] += _dot(ds, q_ref[0, r, :], _TN)

    g.walk(block)

    if dk_scr is not None:
        @pl.when(qi == nq - 1)
        def _final_dkv():
            dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if dq_scr is not None:
        @pl.when(dq_last)
        def _final_dq():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# VMEM budget for the fused backward's resident set; above it the split
# two-kernel path runs instead. 12 MB keeps S=16384 at D=64 (f32) on the
# fused path (~10.5 MB estimated) inside the ~16 MB/core VMEM envelope.
_FUSED_BWD_MAX_BYTES = int(
    os.environ.get("TNN_FLASH_FUSED_BWD_MAX_BYTES", 12 * 2**20))


def _fused_bwd_applicable(sq_p: int, d: int, bq: int = 512, bk: int = 512,
                          itemsize: int = 4) -> bool:
    """Estimate the fused kernel's whole VMEM-resident set — not just the
    full-seq dQ scratch: the dQ OUTPUT block is also full-seq (constant index
    map, so it stays resident), the per-block q/o/do/k/v operands and dk/dv
    outputs are double-buffered by the pipeline, and the dk/dv accumulators
    are f32 scratch. Underestimating here fails inside Mosaic at lowering
    time instead of cleanly taking the split path."""
    if os.environ.get("TNN_FLASH_FUSED_BWD", "1") == "0":
        return False
    dq_bytes = sq_p * d * (itemsize + 4)      # dq out block + f32 accumulator
    blk_in = (3 * bq + 2 * bk) * d * itemsize + bq * 4  # q/o/do, k/v, lse
    blk_out = 2 * bk * d * itemsize                     # dk/dv out blocks
    acc = 2 * bk * d * 4 + bq * 4          # dk/dv f32 scratch, rowsum(dO*O)
    resident = dq_bytes + 2 * (blk_in + blk_out) + acc
    return resident <= _FUSED_BWD_MAX_BYTES


def _flash_bwd(causal, scale, block_q, block_k, block_q_bwd, block_k_bwd,
               clamp_dead, residuals, g):
    _, _, _, mask, off, _, _ = residuals
    return _flash_bwd_launch(
        _ambient(), causal, scale, block_q, block_k, block_q_bwd, block_k_bwd,
        clamp_dead, residuals, g) + _zero_cotangents(mask, off)


@functools.partial(jax.jit, inline=True, static_argnums=tuple(range(8)))
def _flash_bwd_launch(ambient, causal, scale, block_q, block_k, block_q_bwd,
                      block_k_bwd, clamp_dead, residuals, g):
    """Blockwise Pallas backward: never materializes the (S, S) matrix.
    -> (dq, dk, dv)."""
    q, k, v, mask, off, o, lse_row = residuals
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # Fused single-pass backward when the full-seq dQ scratch fits VMEM, at
    # its own block default, or at half of it (S=16384 at D=64 in f32).
    for fused_block in (DEFAULT_BLOCK_FUSED_BWD, DEFAULT_BLOCK_FUSED_BWD // 2):
        bqp, bkp, sq_pf, _ = _block_geometry(
            sq, skv, block_q_bwd if block_q_bwd is not None else fused_block,
            block_k_bwd if block_k_bwd is not None else fused_block)
        if _fused_bwd_applicable(sq_pf, d, bqp, bkp, q.dtype.itemsize):
            return _flash_bwd_fused(causal, scale, bqp, bkp, clamp_dead,
                                    residuals, g)
    bq_bwd, bk_bwd = _bwd_blocks(block_q, block_k, block_q_bwd, block_k_bwd)
    bq, bk, sq_p, skv_p = _block_geometry(sq, skv, bq_bwd, bk_bwd)
    hkv = k.shape[1]
    kv_head = _kv_head_map(h, hkv)

    qf = _pad_to(q.reshape(b * h, sq, d), sq_p, 1)
    kf = _pad_to(k.reshape(b * hkv, skv, d), skv_p, 1)
    vf = _pad_to(v.reshape(b * hkv, skv, d), skv_p, 1)
    of = _pad_to(o.reshape(b * h, sq, d), sq_p, 1)
    dof = _pad_to(g.reshape(b * h, sq, d), sq_p, 1)
    # +inf on padded q rows makes their recomputed p exactly 0, so they add
    # nothing to dK/dV (their dQ rows are sliced off anyway)
    lse = _pad_to(lse_row, sq_p, 1, value=jnp.inf)[:, :, None]

    has_mask = mask is not None
    maskp = (_pad_to(_pad_to(mask, sq_p, 1), skv_p, 2) if has_mask else None)

    interpret = interpret_default()
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk,
                  sub=SUB_TILE, kv_len=skv,
                  has_mask=has_mask, static_off=clamp_dead or not causal,
                  nq=sq_p // bq, nk=skv_p // bk)
    # dead-block DMA elision, same as forward/fused: dq grid (bh, i, j) has
    # its dead k blocks at the END of each j sweep — clamp their fetch index
    # to the row's last live block so the pipeline skips the copy
    if clamp_dead and causal:
        def j_idx(i, j):
            return jnp.minimum(j, (i * bq + bq - 1) // bk)
    else:
        def j_idx(i, j):
            return j
    q_spec = pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, d),
                           lambda bh, i, j: (kv_head(bh), j_idx(i, j), 0),
                           memory_space=pltpu.VMEM)

    in_specs = [_OFF_SPEC, q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec]
    inputs = [off, qf, kf, vf, of, dof, lse]
    if has_mask:
        in_specs.append(_mask_spec(maskp, b, h, bq, bk,
                                   lambda bh, i, j: (i, j_idx(i, j))))
        inputs.append(maskp)
    dq = pl.pallas_call(
        functools.partial(_bwd_kernel, outputs="dq", **common),
        name="tnn_flash_bwd_dq",
        grid=(b * h, sq_p // bq, skv_p // bk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],  # rowsum(dO * O)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)

    # transposed grid: blocks indexed (bh, k block, q block); dead q blocks
    # sit at the START of each i sweep — clamp to the first live row (with
    # the in-range guard for sq < skv)
    if clamp_dead and causal:
        def i_idx(j, i):
            return jnp.minimum(jnp.maximum(i, (j * bk) // bq),
                               sq_p // bq - 1)
    else:
        def i_idx(j, i):
            return i
    qT_spec = pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i_idx(j, i), 0),
                           memory_space=pltpu.VMEM)
    lseT_spec = pl.BlockSpec((1, bq, 1),
                             lambda bh, j, i: (bh, i_idx(j, i), 0),
                             memory_space=pltpu.VMEM)
    kvT_fetch = pl.BlockSpec((1, bk, d),
                             lambda bh, j, i: (kv_head(bh), j, 0),
                             memory_space=pltpu.VMEM)
    # dk/dv are written PER Q HEAD (grid bh), group-summed after the kernel
    kvT_spec = pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0),
                            memory_space=pltpu.VMEM)
    in_specsT = [_OFF_SPEC, qT_spec, kvT_fetch, kvT_fetch, qT_spec, qT_spec,
                 lseT_spec]
    inputsT = [off, qf, kf, vf, of, dof, lse]
    if has_mask:
        in_specsT.append(_mask_spec(maskp, b, h, bq, bk,
                                    lambda bh, j, i: (i_idx(j, i), j)))
        inputsT.append(maskp)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, outputs="dkv", **common),
        name="tnn_flash_bwd_dkv",
        grid=(b * h, skv_p // bk, sq_p // bq),
        in_specs=in_specsT,
        out_specs=[kvT_spec, kvT_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, skv_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, skv_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],  # rowsum(dO * O)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputsT)

    dq = dq[:, :sq].reshape(b, h, sq, d)
    return (dq,) + _group_sum_kv_grads(dk, dv, b, h, hkv, skv, d)


def _group_sum_kv_grads(dk, dv, b, h, hkv, skv, d):
    """Per-q-head dK/dV (b*h, skv_p, d) -> per-kv-head (b, hkv, skv, d):
    the kernels emit each q head's contribution separately (a shared output
    block would be revisited non-consecutively across the grid, which the
    sequential pipeline cannot accumulate), and the group sum runs as one
    XLA reduction here."""
    dk_dt, dv_dt = dk.dtype, dv.dtype
    dk = dk[:, :skv].reshape(b, h, skv, d)
    dv = dv[:, :skv].reshape(b, h, skv, d)
    if h != hkv:
        g = h // hkv
        dk = dk.reshape(b, hkv, g, skv, d).astype(jnp.float32).sum(2)
        dv = dv.reshape(b, hkv, g, skv, d).astype(jnp.float32).sum(2)
    return dk.astype(dk_dt), dv.astype(dv_dt)


def _zero_cotangents(mask, off):
    import numpy as _np

    from jax import dtypes as _jdt

    # mask (bool/int8) and kv_offset (int32) have no gradient; their cotangent
    # type is float0
    dmask = (None if mask is None
             else _np.zeros(mask.shape, _jdt.float0))
    return dmask, _np.zeros(off.shape, _jdt.float0)


def _flash_bwd_fused(causal, scale, bq, bk, clamp_dead, residuals, g):
    """One-sweep backward (see _bwd_kernel, "fused"). Grid (bh, j, i): k/v blocks
    stay VMEM-resident across the inner q loop (constant index map), dK/dV
    write once per j, dQ once per bh from the full-seq scratch."""
    q, k, v, mask, off, o, lse_row = residuals
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    kv_head = _kv_head_map(h, hkv)
    _, _, sq_p, skv_p = _block_geometry(sq, skv, bq, bk)
    bq = min(bq, sq_p)
    bk = min(bk, skv_p)

    qf = _pad_to(q.reshape(b * h, sq, d), sq_p, 1)
    kf = _pad_to(k.reshape(b * hkv, skv, d), skv_p, 1)
    vf = _pad_to(v.reshape(b * hkv, skv, d), skv_p, 1)
    of = _pad_to(o.reshape(b * h, sq, d), sq_p, 1)
    dof = _pad_to(g.reshape(b * h, sq, d), sq_p, 1)
    lse = _pad_to(lse_row, sq_p, 1, value=jnp.inf)[:, :, None]
    has_mask = mask is not None
    maskp = (_pad_to(_pad_to(mask, sq_p, 1), skv_p, 2) if has_mask else None)

    # grid (bh, k block j, q block i) — q-side blocks indexed by i (pos 2).
    # Causal + no kv_offset: q blocks with i < first live row for this k
    # block are all-masked; clamping their fetch index to the first live row
    # repeats the block index so the pipeline elides the DMA (mirrors the
    # forward's dead-block clamp, transposed).
    if clamp_dead and causal:
        # min() guard: with sq < skv a trailing k block's first live row can
        # land past the last q block; those steps are fully dead and must
        # keep fetching an in-range block
        def q_idx(bh, j, i):
            return jnp.minimum(jnp.maximum(i, (j * bk) // bq),
                               sq_p // bq - 1)
    else:
        def q_idx(bh, j, i):
            return i
    q_spec = pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, q_idx(bh, j, i), 0),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, bq, 1),
                            lambda bh, j, i: (bh, q_idx(bh, j, i), 0),
                            memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, d), lambda bh, j, i: (kv_head(bh), j, 0),
                           memory_space=pltpu.VMEM)
    in_specs = [_OFF_SPEC, q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec]
    inputs = [off, qf, kf, vf, of, dof, lse]
    if has_mask:
        in_specs.append(_mask_spec(maskp, b, h, bq, bk,
                                   lambda bh, j, i: (q_idx(bh, j, i), j)))
        inputs.append(maskp)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, outputs="fused", scale=scale, causal=causal, bq=bq,
            bk=bk, sub=SUB_TILE, kv_len=skv, has_mask=has_mask,
            static_off=clamp_dead or not causal, nq=sq_p // bq,
            nk=skv_p // bk),
        name="tnn_flash_bwd_fused",
        grid=(b * h, skv_p // bk, sq_p // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, sq_p, d), lambda bh, j, i: (bh, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, skv_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, skv_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq_p, d), jnp.float32),  # full-seq dQ accumulator
            pltpu.VMEM((bk, d), jnp.float32),    # dK block accumulator
            pltpu.VMEM((bk, d), jnp.float32),    # dV block accumulator
            pltpu.VMEM((bq, 1), jnp.float32),    # rowsum(dO * O) of the block
        ],
        # the dQ scratch carries across the whole (j, i) sweep of one bh, so
        # both inner dims are "arbitrary"; bh segments are independent
        # (re-initialized at (0, 0)). The explicit VMEM budget keeps the
        # full-seq scratch from tripping Mosaic's conservative default check
        # at S=16384.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 2**20),
        interpret=interpret_default(),
    )(*inputs)

    dq = dq[:, :sq].reshape(b, h, sq, d)
    return (dq,) + _group_sum_kv_grads(dk, dv, b, h, hkv, skv, d)


_flash.defvjp(_flash_fwd, _flash_bwd)
