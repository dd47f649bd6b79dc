"""Ring attention — sequence/context parallelism over the "seq" mesh axis.

Beyond the reference: TNN has NO sequence/context parallelism of any kind (verified in
SURVEY.md §5 — its long-context story is single-device flash attention at fixed
seq_len=1024). Here sequences shard over devices; K/V blocks rotate around the ring via
collective-permute over ICI while each device accumulates its queries' attention with
online softmax (the flash-attention recurrence across devices). Memory per device is
O(S/ring); the full sequence never materialises anywhere.

Differentiable: built from jnp ops + ppermute, so jax.grad produces the reverse ring.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops import softmax_merge
from . import mesh as mesh_lib


def _ring_attention_local(q, k, v, *, axis: str, causal: bool, scale: float):
    """Per-device body under shard_map. q: (B, H, S_local, D); k/v may carry
    H_kv < H heads (GQA) — the blocks ROTATE at H_kv size (the ICI-traffic
    win scales with the cache shrink) and repeat to H only at compute."""
    ring = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    s_local = q.shape[-2]
    group = q.shape[1] // k.shape[1]

    qpos = (idx * s_local + jnp.arange(s_local))[:, None]  # global query positions

    b, h, s, d = q.shape
    m0 = jnp.full((b, h, s, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, s, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), jnp.float32)

    perm = [(i, (i + 1) % ring) for i in range(ring)]

    def attend(m_prev, l_prev, acc, k_blk, v_blk, r):
        """One online-softmax block update against the K/V block held after r hops."""
        # after r hops this device holds the block originally owned by (idx - r) % ring
        owner = jnp.mod(idx - r, ring)
        if group > 1:  # GQA: broadcast kv heads at compute (XLA folds it)
            k_blk = jnp.repeat(k_blk, group, axis=1)
            v_blk = jnp.repeat(v_blk, group, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = (owner * s_local + jnp.arange(s_local))[None, :]
            logits = jnp.where(qpos >= kpos, logits, -1e30)
        # the online-softmax recurrence lives in ops.softmax_merge — the
        # single source of the partitioned-attention math, shared with the
        # sequence-parallel serving combine (serving/sp.py)
        return softmax_merge.block_update(m_prev, l_prev, acc, logits, v_blk)

    def block(carry, r):
        # lax.scan (not a Python loop): one compiled body regardless of ring size,
        # so compile time stays flat as the ring grows.
        m_prev, l_prev, acc, k_blk, v_blk = carry
        m_new, l_new, acc = attend(m_prev, l_prev, acc, k_blk, v_blk, r)
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (m_new, l_new, acc, k_blk, v_blk), None

    # Scan the first ring-1 blocks (each ending with a K/V hop); the final block
    # attends outside the scan so no ICI hop is wasted shipping K/V a full circle.
    (m, l, acc, k_last, v_last), _ = jax.lax.scan(
        block, (m0, l0, acc0, k, v), jnp.arange(ring - 1))
    m, l, acc = attend(m, l, acc, k_last, v_last, ring - 1)
    return softmax_merge.finalize(m, l, acc, q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "seq", causal: bool = False,
                   scale: Optional[float] = None, batch_axis: Optional[str] = None):
    """Attention over (B, H, S, D) tensors whose S dim is sharded over ``axis``.

    Call with global arrays sharded P(None, None, axis, None); returns the same
    sharding. S must divide evenly by the ring size. ``batch_axis`` (one axis
    name or a tuple, e.g. ("data", "fsdp")) additionally shards the batch dim:
    each batch shard runs its own ring — without it, a batch-sharded input
    would be all-gathered at the shard_map boundary.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    ring = mesh_lib.axis_size(mesh, axis)
    if q.shape[-2] % ring:
        raise ValueError(f"seq len {q.shape[-2]} not divisible by ring size {ring}")
    if q.shape[1] % k.shape[1] or v.shape[1] != k.shape[1]:
        raise ValueError(f"q has {q.shape[1]} heads but k/v have "
                         f"{k.shape[1]}/{v.shape[1]}; need H % H_kv == 0")
    body = functools.partial(_ring_attention_local, axis=axis, causal=causal, scale=scale)
    return mesh_lib.seq_shard_map(body, mesh, axis, batch_axis)(q, k, v)
