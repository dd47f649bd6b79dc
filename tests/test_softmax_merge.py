"""ops.softmax_merge: the shared partitioned-attention math, standalone.

The ring-attention and SP-serving tests gate end-to-end behavior; these pin
the algebra itself — associativity against a single-pass reference, the
empty-partition identity, and bf16 tolerance — so a regression points at
the merge, not at whichever caller noticed first.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tnn_tpu.ops import softmax_merge as sm
from tnn_tpu.parallel import mesh as mesh_lib


def _state(logits, v):
    """Single-block partial state from scratch (the kernel's view)."""
    m0 = jnp.full(logits.shape[:-1] + (1,), sm.NEG_INF, jnp.float32)
    l0 = jnp.zeros_like(m0)
    acc0 = jnp.zeros(logits.shape[:-1] + (v.shape[-1],), jnp.float32)
    return sm.block_update(m0, l0, acc0, logits, v)


def _ref(logits, v):
    """One-shot softmax over the full (concatenated) row."""
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)


def test_merge_matches_single_pass(rng):
    rs = np.random.RandomState(0)
    parts = [(jnp.asarray(rs.randn(2, 3, 4, 8), jnp.float32),
              jnp.asarray(rs.randn(2, 3, 8, 16), jnp.float32))
             for _ in range(3)]
    a, b, c = (_state(lg, v) for lg, v in parts)
    merged = sm.merge(a, sm.merge(b, c))
    full = _ref(jnp.concatenate([lg for lg, _ in parts], axis=-1),
                jnp.concatenate([v for _, v in parts], axis=-2))
    out = sm.finalize(*merged)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=1e-5, atol=1e-6)
    # commutative + associative the other way around too
    alt = sm.finalize(*sm.merge(sm.merge(c, a), b))
    np.testing.assert_allclose(np.asarray(alt), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_empty_partition_is_identity(rng):
    rs = np.random.RandomState(1)
    lg = jnp.asarray(rs.randn(1, 2, 4, 8), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 8, 16), jnp.float32)
    a = _state(lg, v)
    empty = (jnp.full_like(a[0], sm.NEG_INF), jnp.zeros_like(a[1]),
             jnp.zeros_like(a[2]))
    for pair in (sm.merge(a, empty), sm.merge(empty, a)):
        for got, want in zip(pair, a):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=0)
    # all partitions empty: output is 0, not NaN (the l == 0 guard)
    zero = sm.finalize(*sm.merge(empty, empty))
    assert np.all(np.asarray(zero) == 0.0)


def test_bf16_values_tolerance(rng):
    """bf16 V flows through block_update (acc accumulates f32); the merged
    result must track the f32 reference inside bf16 resolution."""
    rs = np.random.RandomState(2)
    lg1 = jnp.asarray(rs.randn(1, 2, 4, 8), jnp.float32)
    lg2 = jnp.asarray(rs.randn(1, 2, 4, 8), jnp.float32)
    v1 = jnp.asarray(rs.randn(1, 2, 8, 16), jnp.float32)
    v2 = jnp.asarray(rs.randn(1, 2, 8, 16), jnp.float32)
    out = sm.finalize(*sm.merge(
        _state(lg1, v1.astype(jnp.bfloat16)),
        _state(lg2, v2.astype(jnp.bfloat16))))
    full = _ref(jnp.concatenate([lg1, lg2], axis=-1),
                jnp.concatenate([v1, v2], axis=-2))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(full), rtol=2e-2, atol=2e-2)


def test_merge_psum_matches_merge(rng):
    """The cross-mesh combine (normalized outs + stats, psum-weighted) must
    agree with the host-side pairwise merge of the same partials — including
    a shard whose every row is empty."""
    if jax.device_count() < 4:
        pytest.skip("needs the 4+ device virtual mesh")
    rs = np.random.RandomState(3)
    sp = 4
    lgs = jnp.asarray(rs.randn(sp, 1, 2, 4, 8), jnp.float32)
    vs = jnp.asarray(rs.randn(sp, 1, 2, 8, 16), jnp.float32)
    # shard 3 sees no keys at all: dead logits -> empty state
    lgs = lgs.at[3].set(sm.NEG_INF)
    mesh = mesh_lib.make_mesh(seq=sp)
    P = jax.sharding.PartitionSpec

    def body(lg, v):
        m, l, acc = _state(lg[0], v[0])  # noqa: E741
        out = sm.finalize(m, l, acc)
        return sm.merge_psum(out, m, l, "seq")[None]

    out = jax.shard_map(
        body, mesh=mesh, in_specs=(P("seq"), P("seq")),
        out_specs=P("seq"), check_vma=False)(lgs, vs)
    states = [_state(lgs[i], vs[i]) for i in range(sp)]
    want = states[0]
    for s in states[1:]:
        want = sm.merge(want, s)
    want = sm.finalize(*want)
    for i in range(sp):  # combine is replicated row-wise across shards
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
