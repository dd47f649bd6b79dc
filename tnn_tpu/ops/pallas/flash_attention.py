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

Falls back to interpret mode off-TPU so the same code path tests on CPU.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

# Tuned on v5e (honest difference-timing, B=8/H=12/D=64). Forward is best at
# 1024/1024 (S=1024: 0.42ms = 30.9 TFLOP/s; S=4096: 5.36ms = 38.5 TFLOP/s —
# 4-5x the stock jax.experimental pallas flash kernel on the same shapes, and
# ~78% of the D=64-contraction MXU ceiling). The backward prefers smaller q
# blocks (S=4096 fwd+bwd: 512/1024 -> 36.2 TFLOP/s-equiv vs 28.1 at
# 1024/1024), so fwd and bwd carry separate block defaults. 2048-wide blocks
# fail to compile (VMEM).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
DEFAULT_BLOCK_Q_BWD = 512
DEFAULT_BLOCK_K_BWD = 1024
_NEG_INF = -1e30


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                bq: int, bk: int, kv_len: int, has_mask: bool):
    if has_mask:
        mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        mask_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = None, rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)  # (bq, 1)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    off = off_ref[0]  # absolute position of q row 0 in the kv sequence
    # Causal: a key block strictly above the diagonal contributes nothing.
    live = (k_start <= q_start + off + bq - 1) if causal else True

    @pl.when(live)
    def _block():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len  # padded keys
        if causal:
            qpos = off + q_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            mask = jnp.logical_and(mask, qpos >= kpos)
        if has_mask:
            mask = jnp.logical_and(mask, mask_ref[0] != 0)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:]                              # (bq, 1)
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk) f32
        # rows with NO live key so far have m_new == _NEG_INF, which would
        # give the masked entries exp(0) = 1; zero them explicitly so fully
        # masked rows end with l == 0 (-> output 0, lse +inf)
        p = jnp.where(mask, p, 0.0)
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + l_cur
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == nk - 1)
    def _final():
        l = l_scr[:]
        lsafe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[:] / lsafe).astype(o_ref.dtype)
        # logsumexp per row for the backward; +inf on fully-masked/padded rows
        # makes their p = exp(s - L) exactly 0 there (never NaN)
        m = m_scr[:]
        # compact (bq, 1) column — 4 bytes/row in HBM end to end, vs the
        # lane-replicated 128-lane layout that cost ~400MB transient f32 at
        # B=8/H=12/S=8k (Mosaic pads narrow minor dims in VMEM transparently)
        lse_ref[0] = jnp.where(l > 0.0, m + jnp.log(lsafe), jnp.inf)


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

    Forward and backward take independent block geometry (the backward's three
    matmul chain prefers smaller q blocks — see the tuning note above).
    ``block_*_bwd=None`` resolves to min(caller's fwd block, tuned bwd
    default): a caller shrinking blocks to fit VMEM shrinks the backward too,
    while the stock defaults give the tuned (512, 1024) backward.

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


def _flash_fwd(q, k, v, mask, off, causal, scale, block_q, block_k,
               block_q_bwd=None, block_k_bwd=None, clamp_dead=False):
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
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, kv_len=skv,
                               has_mask=mask is not None)
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
        scratch_shapes=[
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


def _attn_probs(q, k, lse_col, k_start, q_start, off, mask_blk, *, scale,
                causal, bq, bk, kv_len):
    """Recompute P_ij = exp(S_ij - L_i) for one (q block, k block) tile, masked."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < kv_len
    if causal:
        qpos = off + q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = jnp.logical_and(mask, qpos >= kpos)
    if mask_blk is not None:
        mask = jnp.logical_and(mask, mask_blk != 0)
    s = jnp.where(mask, s, _NEG_INF)
    # L = +inf on fully-masked/padded rows -> p = 0 there (see _fwd_kernel)
    return jnp.exp(s - lse_col)


def _bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   *rest, scale, causal, bq, bk, kv_len, has_mask):
    if has_mask:
        mask_ref, dq_ref, dq_scr = rest
    else:
        mask_ref, (dq_ref, dq_scr) = None, rest
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start, k_start = qi * bq, ki * bk
    off = off_ref[0]
    live = (k_start <= q_start + off + bq - 1) if causal else True

    @pl.when(live)
    def _block():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse_col = lse_ref[0]                           # (bq, 1), compact
        do32 = do.astype(jnp.float32)
        # delta_i = rowsum(dO_i * O_i), recomputed per block (elementwise, cheap)
        delta = jnp.sum(do32 * o_ref[0].astype(jnp.float32), axis=1,
                        keepdims=True)
        p = _attn_probs(q, k, lse_col, k_start, q_start, off,
                        mask_ref[0] if has_mask else None, scale=scale,
                        causal=causal, bq=bq, bk=bk, kv_len=kv_len)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                  # (bq, bk) f32
        dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    *rest, scale, causal, bq, bk, kv_len, has_mask):
    if has_mask:
        mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        mask_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = None, rest
    # grid: (bh, k_blocks, q_blocks) — accumulate over q for one k/v block
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start, k_start = qi * bq, ki * bk
    off = off_ref[0]
    live = (k_start <= q_start + off + bq - 1) if causal else True

    @pl.when(live)
    def _block():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse_col = lse_ref[0]                           # (bq, 1), compact
        delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                        axis=1, keepdims=True)
        p = _attn_probs(q, k, lse_col, k_start, q_start, off,
                        mask_ref[0] if has_mask else None, scale=scale,
                        causal=causal, bq=bq, bk=bk, kv_len=kv_len)
        pt = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(pt, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      *rest, scale, causal, bq, bk, kv_len, has_mask):
    """Single-pass backward: dQ, dK, dV in ONE sweep, 5 matmuls per live tile
    (the FlashAttention-2 ideal) vs 7 across the split dq/dkv kernels (S and
    dO@V^T were each computed twice). Grid (bh, k block j, q block i): dK/dV
    accumulate in per-block scratch over the inner i loop; dQ accumulates in a
    FULL-SEQUENCE f32 VMEM scratch (sq x d = 2 MB at S=8192/D=64 — the cheap
    side; dK+dV would need twice that) and is written out once per bh. The
    TPU grid is sequential per core, which is what makes the whole-sweep
    scratch accumulation sound."""
    if has_mask:
        mask_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    else:
        mask_ref, (dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = None, rest
    ki, qi = pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start, k_start = qi * bq, ki * bk
    off = off_ref[0]
    live = (k_start <= q_start + off + bq - 1) if causal else True

    @pl.when(live)
    def _block():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse_col = lse_ref[0]                           # (bq, 1), compact
        delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                        axis=1, keepdims=True)
        p = _attn_probs(q, k, lse_col, k_start, q_start, off,
                        mask_ref[0] if has_mask else None, scale=scale,
                        causal=causal, bq=bq, bk=bk, kv_len=kv_len)
        pt = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(pt, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dq_scr[pl.ds(q_start, bq), :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _final_dkv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == nk - 1, qi == nq - 1))
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
    acc = 2 * bk * d * 4                                # dk/dv f32 scratch
    resident = dq_bytes + 2 * (blk_in + blk_out) + acc
    return resident <= _FUSED_BWD_MAX_BYTES


def _flash_bwd(causal, scale, block_q, block_k, block_q_bwd, block_k_bwd,
               clamp_dead, residuals, g):
    """Blockwise Pallas backward: never materializes the (S, S) matrix."""
    q, k, v, mask, off, o, lse_row = residuals
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # Fused single-pass backward when the full-seq dQ scratch fits VMEM;
    # its own block default (512, 512) keeps the bq x bk f32 intermediates
    # ~1 MB so blocks + dq scratch + outputs stay inside ~16 MB at S=16384.
    bq_f = block_q_bwd if block_q_bwd is not None else 512
    bk_f = block_k_bwd if block_k_bwd is not None else 512
    bqp, bkp, sq_pf, _ = _block_geometry(sq, skv, bq_f, bk_f)
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
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, kv_len=skv,
                  has_mask=has_mask)
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
        functools.partial(_bwd_dq_kernel, **common),
        name="tnn_flash_bwd_dq",
        grid=(b * h, sq_p // bq, skv_p // bk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
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
        functools.partial(_bwd_dkv_kernel, **common),
        name="tnn_flash_bwd_dkv",
        grid=(b * h, skv_p // bk, sq_p // bq),
        in_specs=in_specsT,
        out_specs=[kvT_spec, kvT_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, skv_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, skv_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputsT)

    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk, dv = _group_sum_kv_grads(dk, dv, b, h, hkv, skv, d)
    dmask, doff = _zero_cotangents(mask, off)
    return dq, dk, dv, dmask, doff


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
    """One-sweep backward (see _bwd_fused_kernel). Grid (bh, j, i): k/v blocks
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
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, kv_len=skv, has_mask=has_mask),
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
    dk, dv = _group_sum_kv_grads(dk, dv, b, h, hkv, skv, d)
    dmask, doff = _zero_cotangents(mask, off)
    return dq, dk, dv, dmask, doff


_flash.defvjp(_flash_fwd, _flash_bwd)
