"""Attention tests — numeric reference checks (parity intent: attention_block_test.cpp)
plus pallas-vs-xla differential testing (the reference's CPU-vs-GPU pattern)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tnn_tpu import nn
from tnn_tpu.core import dtypes as dt
from tnn_tpu.nn.attention import sdpa

F32 = dt.FP32


def _ref_attention(q, k, v, causal=False):
    """NumPy reference."""
    b, h, s, d = q.shape
    skv = k.shape[2]
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        mask = np.tril(np.ones((s, skv), bool), k=skv - s)
        logits = np.where(mask, logits, -1e9)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_sdpa_matches_numpy(causal):
    rs = np.random.RandomState(0)
    q = rs.randn(2, 3, 16, 8).astype(np.float32)
    k = rs.randn(2, 3, 16, 8).astype(np.float32)
    v = rs.randn(2, 3, 16, 8).astype(np.float32)
    out = sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), _ref_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 200])  # aligned and ragged
def test_flash_attention_matches_xla(causal, seq):
    """Differential: pallas blockwise kernel vs XLA path (reference pattern:
    benchmarks/gemm_benchmark.cpp check_match)."""
    rs = np.random.RandomState(1)
    shape = (1, 2, seq, 64)
    q = jnp.asarray(rs.randn(*shape), jnp.float32)
    k = jnp.asarray(rs.randn(*shape), jnp.float32)
    v = jnp.asarray(rs.randn(*shape), jnp.float32)
    ref = sdpa(q, k, v, causal=causal, backend="xla")
    out = sdpa(q, k, v, causal=causal, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_attention_grads_match_xla():
    rs = np.random.RandomState(2)
    shape = (1, 2, 128, 32)
    q = jnp.asarray(rs.randn(*shape), jnp.float32)
    k = jnp.asarray(rs.randn(*shape), jnp.float32)
    v = jnp.asarray(rs.randn(*shape), jnp.float32)

    def loss_xla(q, k, v):
        return jnp.sum(sdpa(q, k, v, causal=True, backend="xla") ** 2)

    def loss_pallas(q, k, v):
        return jnp.sum(sdpa(q, k, v, causal=True, backend="pallas") ** 2)

    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gx, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [130, 256])  # 130 exercises q/k padding rows
def test_flash_backward_blockwise_matches_xla(causal, seq):
    """The Pallas dq/dk/dv kernels (multi-block path, block 128 over seq>128)
    vs XLA autodiff — covers causal block skipping and padded-row handling."""
    rs = np.random.RandomState(3)
    shape = (2, 2, seq, 64)
    q = jnp.asarray(rs.randn(*shape), jnp.float32)
    k = jnp.asarray(rs.randn(*shape), jnp.float32)
    v = jnp.asarray(rs.randn(*shape), jnp.float32)
    g = jnp.asarray(rs.randn(*shape), jnp.float32)

    from tnn_tpu.ops.pallas.flash_attention import flash_attention

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal, None, 128, 128), g)

    def loss_xla(q, k, v):
        return jnp.vdot(sdpa(q, k, v, causal=causal, backend="xla"), g)

    gp = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-3, err_msg=name)


def test_flash_backward_independent_geometry():
    """Backward block geometry independent of the forward's: fwd runs a single
    256-block while bwd runs 64-blocks over seq=200 — exercising the +inf
    re-padding of the unpadded lse residual (rows 200..255 must contribute
    p=0 to dK/dV, not NaN/garbage)."""
    rs = np.random.RandomState(7)
    shape = (1, 2, 200, 64)
    q = jnp.asarray(rs.randn(*shape), jnp.float32)
    k = jnp.asarray(rs.randn(*shape), jnp.float32)
    v = jnp.asarray(rs.randn(*shape), jnp.float32)
    g = jnp.asarray(rs.randn(*shape), jnp.float32)

    from tnn_tpu.ops.pallas.flash_attention import flash_attention

    def loss_flash(q, k, v):
        return jnp.vdot(
            flash_attention(q, k, v, True, None, 256, 256, 64, 64), g)

    def loss_xla(q, k, v):
        return jnp.vdot(sdpa(q, k, v, causal=True, backend="xla"), g)

    gp = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gp, gx):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-3, err_msg=name)


def test_flash_backward_memory_scales_with_blocks():
    """The backward must not materialize the (S, S) matrix: its jaxpr contains
    no S x S-shaped intermediate (the whole point vs the XLA recompute path)."""
    S = 512
    q = jnp.zeros((1, 1, S, 64), jnp.float32)

    from tnn_tpu.ops.pallas.flash_attention import flash_attention

    # block 128 forces the MULTI-block path (4x4 grid): any full-sequence
    # materialization would show up as an (S, S) intermediate in the jaxpr
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: flash_attention(q, k, v, True, None,
                                                 128, 128).sum(),
                 argnums=(0, 1, 2)))(q, q, q)
    shapes = [v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars
              if hasattr(v.aval, "shape")]
    assert not any(s.count(S) >= 2 for s in shapes), (
        f"found S x S intermediate in backward: "
        f"{[s for s in shapes if s.count(S) >= 2]}")


def test_mha_shapes_and_causality(rng):
    mha = nn.MultiHeadAttention(num_heads=4, causal=True, policy=F32)
    v = mha.init(rng, (2, 10, 32))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 10, 32), jnp.float32)
    y = mha(v, x)
    assert y.shape == (2, 10, 32)
    # causality: output at position t must not depend on inputs at positions > t
    x2 = x.at[:, 7:].set(0.0)
    y2 = mha(v, x2)
    np.testing.assert_allclose(np.asarray(y[:, :7]), np.asarray(y2[:, :7]),
                               rtol=1e-4, atol=1e-5)


def test_mha_cached_decode_matches_full(rng):
    """KV-cache decode must reproduce full-sequence forward exactly."""
    mha = nn.MultiHeadAttention(num_heads=2, causal=True, policy=F32)
    v = mha.init(rng, (1, 8, 16))
    x = jnp.asarray(np.random.RandomState(4).randn(1, 8, 16), jnp.float32)
    full = mha(v, x)
    cache = mha.init_cache(1, 8, 16)
    # prefill 5, then decode 3 one at a time
    out_pre, cache = mha.apply_cached(v, x[:, :5], cache, 0)
    outs = [out_pre]
    for t in range(5, 8):
        o, cache = mha.apply_cached(v, x[:, t:t + 1], cache, t)
        outs.append(o)
    stitched = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                               rtol=1e-4, atol=1e-5)


def test_gpt_block_roundtrip_and_forward(rng):
    from tnn_tpu.core.module import module_from_config

    blk = nn.GPTBlock(num_heads=4, policy=F32)
    cfg = blk.get_config()
    assert module_from_config(cfg).get_config() == cfg
    v = blk.init(rng, (2, 6, 32))
    y = blk(v, jnp.asarray(np.random.RandomState(5).randn(2, 6, 32), jnp.float32))
    assert y.shape == (2, 6, 32)


class TestFlashMaskAndOffset:
    """mask/kv_offset support in the Pallas kernel (round-4: cached decode and
    masked attention no longer fall back to XLA)."""

    def _qkv(self, b=2, h=2, sq=64, skv=None, d=32, seed=0):
        rs = np.random.RandomState(seed)
        skv = skv or sq
        return (jnp.asarray(rs.randn(b, h, sq, d), jnp.float32),
                jnp.asarray(rs.randn(b, h, skv, d), jnp.float32),
                jnp.asarray(rs.randn(b, h, skv, d), jnp.float32))

    @pytest.mark.parametrize("causal,mask_shape", [
        (False, (2, 1, 64, 64)),   # padding mask, broadcast over heads
        (True, (2, 2, 64, 64)),    # per-head mask composed with causal
        (False, (64, 64)),         # shared 2-D mask
    ])
    def test_masked_forward_matches_xla(self, causal, mask_shape):
        from tnn_tpu.nn.attention import local_xla_attention
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        mask = jnp.asarray(np.random.RandomState(1).rand(*mask_shape) > 0.25)
        ref = local_xla_attention(q, k, v, causal=causal, mask=mask)
        got = flash_attention(q, k, v, causal, None, 32, 32, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_fully_masked_rows_are_zero(self):
        """Convention check: a row that attends to nothing outputs 0 (the XLA
        path's bare softmax would silently give uniform attention)."""
        from tnn_tpu.nn.attention import local_xla_attention
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        mask = np.ones((64, 64), bool)
        mask[7, :] = False  # row 7 attends to nothing
        mask = jnp.asarray(mask)
        for fn in (lambda: flash_attention(q, k, v, False, None, 32, 32,
                                           mask=mask),
                   lambda: local_xla_attention(q, k, v, mask=mask)):
            out = np.asarray(fn())
            assert np.all(out[:, :, 7] == 0)
            assert np.isfinite(out).all()

    def test_masked_grads_match_xla(self):
        from tnn_tpu.nn.attention import local_xla_attention
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        mask = jnp.asarray(np.random.RandomState(2).rand(2, 2, 64, 64) > 0.2)

        def g(fn):
            return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                            argnums=(0, 1, 2))(q, k, v)

        gf = g(lambda q, k, v: flash_attention(q, k, v, True, None, 32, 32,
                                               32, 32, mask=mask))
        gx = g(lambda q, k, v: local_xla_attention(q, k, v, causal=True,
                                                   mask=mask))
        for a, b in zip(gf, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_kv_offset_decode_matches_xla(self):
        """S_q=4 new tokens attending into a 64-slot cache at offset 60 — the
        cached-decode geometry, including a TRACED offset."""
        from tnn_tpu.nn.attention import local_xla_attention
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv(sq=4, skv=64)
        off = jnp.asarray(60, jnp.int32)
        ref = local_xla_attention(q, k, v, causal=True, kv_offset=off)
        got = jax.jit(lambda q, k, v, off: flash_attention(
            q, k, v, True, None, 32, 32, kv_offset=off))(q, k, v, off)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_cached_decode_uses_pallas_backend(self, rng):
        """A backend='pallas' MHA decodes through the flash kernel (no
        NotImplementedError) and matches the full forward."""
        mha = nn.MultiHeadAttention(num_heads=4, causal=True,
                                    backend="pallas", policy=F32)
        x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 32), jnp.float32)
        v = mha.init(rng, x.shape)
        full = mha(v, x)
        cache = mha.init_cache(2, 8, 32)
        out, cache = mha.apply_cached(v, x[:, :5], cache, 0)
        outs = [out]
        for t in range(5, 8):
            o, cache = mha.apply_cached(v, x[:, t:t + 1], cache, t)
            outs.append(o)
        stitched = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                                   rtol=1e-4, atol=1e-5)


def test_fused_bwd_matches_split_bwd(monkeypatch):
    """The single-pass fused backward (5 matmuls/tile, full-seq dQ scratch)
    must produce the same gradients as the split dq/dkv kernels, including
    with a padding mask, ragged seq, and kv_offset."""
    from tnn_tpu.ops.pallas import flash_attention as fa

    rs = np.random.RandomState(11)
    b, h, sq, skv, d = 2, 2, 200, 256, 64
    q = jnp.asarray(rs.randn(b, h, sq, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, skv, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, skv, d), jnp.float32)
    g = jnp.asarray(rs.randn(b, h, sq, d), jnp.float32)
    mask = jnp.asarray(rs.rand(b, 1, sq, skv) > 0.1)

    def grads(q, k, v):
        def loss(q, k, v):
            return jnp.vdot(fa.flash_attention(
                q, k, v, True, None, 128, 128, 64, 64, mask=mask,
                kv_offset=skv - sq), g)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("TNN_FLASH_FUSED_BWD", "0")
    split = grads(q, k, v)
    monkeypatch.setenv("TNN_FLASH_FUSED_BWD", "1")
    fused = grads(q, k, v)
    assert fa._fused_bwd_applicable(256, d)  # the fused path really ran
    for name, a, b_ in zip("dq dk dv".split(), fused, split):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def test_fused_bwd_causal_short_query_no_offset(monkeypatch):
    """causal + sq < skv + kv_offset=None: trailing k blocks' first live q row
    lands past the last q block; the fused backward's clamped fetch index must
    stay in range (regression: unguarded max() overflowed the q BlockSpec)."""
    from tnn_tpu.ops.pallas import flash_attention as fa

    rs = np.random.RandomState(13)
    q = jnp.asarray(rs.randn(1, 2, 100, 64), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 256, 64), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 256, 64), jnp.float32)
    g = jnp.asarray(rs.randn(1, 2, 100, 64), jnp.float32)

    def grads(q, k, v):
        def loss(q, k, v):
            return jnp.vdot(fa.flash_attention(
                q, k, v, True, None, 64, 64, 64, 64), g)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("TNN_FLASH_FUSED_BWD", "0")
    split = grads(q, k, v)
    monkeypatch.setenv("TNN_FLASH_FUSED_BWD", "1")
    fused = grads(q, k, v)
    for name, a, b_ in zip("dq dk dv".split(), fused, split):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


SUB = 16   # the sub-tile the tests shrink to: a block of 64 is a 4 x 4 plan


@pytest.fixture
def small_sub_tiles(monkeypatch):
    from tnn_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "SUB_TILE", SUB)
    return fa


# name -> (sq, skv, causal, kv heads of 4, extra). Blocks of 64 (forward and
# backward): "one_block" is the training call, spans static and unrolled;
# a grid of several blocks, an offset or a mask makes them traced.
SUB_TILE_CASES = {
    "one_block": (64, 64, True, 4, None),
    "grid_2x2": (128, 128, True, 4, None),
    "padded_last_key_tile": (72, 72, True, 4, None),
    "short_query_no_offset": (32, 64, True, 4, None),
    "static_offset": (32, 64, True, 4, "offset"),
    "traced_offset": (32, 64, True, 4, "traced_offset"),
    "padding_mask": (64, 64, True, 4, "mask"),
    "gqa_4_to_1": (64, 64, True, 1, None),
    "non_causal": (64, 64, False, 4, None),
    "non_causal_padded": (72, 72, False, 4, None),
}


@pytest.mark.parametrize("fused", ["1", "0"])
@pytest.mark.parametrize("case", sorted(SUB_TILE_CASES))
def test_sub_tiles_match_xla(small_sub_tiles, monkeypatch, case, fused):
    """Forward and dq / dk / dv through the sub-tile walk (skip above the
    diagonal, masks only where a dead element is) against the XLA path."""
    from tnn_tpu.nn.attention import local_xla_attention

    fa = small_sub_tiles
    monkeypatch.setenv("TNN_FLASH_FUSED_BWD", fused)
    sq, skv, causal, hkv, extra = SUB_TILE_CASES[case]
    rs = np.random.RandomState(31)
    q = jnp.asarray(rs.randn(2, 4, sq, 32), jnp.float32)
    k = jnp.asarray(rs.randn(2, hkv, skv, 32), jnp.float32)
    v = jnp.asarray(rs.randn(2, hkv, skv, 32), jnp.float32)
    g = jnp.asarray(rs.randn(2, 4, sq, 32), jnp.float32)
    mask = None
    if extra == "mask":     # every row keeps key 0: no row is fully masked
        mask = jnp.asarray((rs.rand(2, 1, sq, skv) > 0.3)
                           | (np.arange(skv) == 0))
    off = None if extra not in ("offset", "traced_offset") else skv - sq

    def both(off):
        def run(attn):
            def loss(q, k, v):
                out = attn(q, k, v)
                return jnp.vdot(out, g), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads
        return (run(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal, None, 64, 64, 64, 64, mask=mask,
                    kv_offset=off)),
                run(lambda q, k, v: local_xla_attention(
                    q, k, v, causal=causal, mask=mask, kv_offset=off)))

    if extra == "traced_offset":
        got, want = jax.jit(both)(jnp.asarray(off, jnp.int32))
    else:
        got, want = both(off)
    for name, a, b in zip("out dq dk dv".split(), got, want):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_sub_tiles_fully_masked_row_reads_zero_and_inf_lse(small_sub_tiles):
    """A row whose keys are all masked: output 0, lse +inf (its p is then 0
    in the backward), with the other rows untouched by it."""
    fa = small_sub_tiles
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(1, 2, 64, 32), jnp.float32)
               for _ in range(3))
    mask = np.ones((64, 64), bool)
    mask[37, :] = False
    mask = fa._norm_mask(jnp.asarray(mask), 1, 2, 64, 64)
    out, res = fa._flash_fwd(q, k, v, mask, jnp.zeros((1,), jnp.int32), True,
                             None, 64, 64, clamp_dead=True)
    lse = np.asarray(res[-1])
    assert np.all(np.asarray(out)[:, :, 37] == 0)
    assert np.all(np.isposinf(lse[:, 37]))
    assert np.isfinite(np.delete(lse, 37, axis=1)).all()
    assert np.isfinite(np.asarray(out)).all()


# (sq, skv, block_q, block_k, tq, tk): blocks and the sub-tiles inside them
SPAN_GEOMETRIES = [
    (1024, 1024, 1024, 1024, 256, 256),   # the training cell's call
    (1024, 1024, 1024, 1024, 256, 512),
    (1024, 1024, 1024, 1024, 512, 128),
    (1024, 1024, 512, 512, 256, 256),
    (1024, 1024, 512, 1024, 128, 256),    # the split backward's blocks
    (1024, 1024, 512, 1024, 512, 1024),   # ... as they run: one sub-tile
    (64, 64, 64, 64, 16, 16),
    (72, 72, 64, 64, 16, 16),             # padding in the last key block
    (72, 72, 64, 64, 64, 64),
    (100, 256, 64, 64, 16, 32),           # sq < skv
    (200, 200, 1024, 1024, 200, 200),     # a block no sub-tile divides
    (1100, 1100, 1024, 1024, 256, 256),   # sub-tiles that are all padding
    (4096, 4096, 1024, 1024, 1024, 1024),
]


def _tile_kinds(sq_p, skv_p, skv, tq, tk, causal):
    """Brute force over the mask: 0 a sub-tile with every element live, 1
    with a live and a dead one, 2 with none live. Padded keys are dead;
    padded query rows are rows like any other (the kernels do not mask
    them: what they read is sliced off)."""
    live = np.arange(skv_p)[None, :] < skv
    if causal:
        live = live & (np.arange(skv_p)[None, :] <= np.arange(sq_p)[:, None])
    tiles = np.broadcast_to(live, (sq_p, skv_p)).reshape(
        sq_p // tq, tq, skv_p // tk, tk)
    return np.where(tiles.all((1, 3)), 0, np.where(tiles.any((1, 3)), 1, 2))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("geometry", SPAN_GEOMETRIES,
                         ids=lambda g: "x".join(map(str, g)))
def test_sub_tile_spans_match_the_mask(geometry, causal):
    """The span the kernels are built from (the query sub-tiles of a key
    sub-tile) against a brute-force reading of the mask: no sub-tile with a
    live element is left out, none with a dead element runs unmasked."""
    from tnn_tpu.ops.pallas import flash_attention as fa

    sq, skv, block_q, block_k, tq, tk = geometry
    bq, bk, sq_p, skv_p = fa._block_geometry(sq, skv, block_q, block_k)
    na, nc = bq // tq, bk // tk
    got = np.full((sq_p // tq, skv_p // tk), 2)
    for qb in range(sq_p // bq):
        for kb in range(skv_p // bk):
            delta = qb * bq - kb * bk if causal else None
            for c in range(nc):
                a_first, a_full = fa._query_span(c, delta, skv - kb * bk, tq,
                                                 tk, na)
                assert 0 <= a_first <= a_full <= na
                got[qb * na + a_first:qb * na + a_full, kb * nc + c] = 1
                got[qb * na + a_full:(qb + 1) * na, kb * nc + c] = 0
    np.testing.assert_array_equal(
        got, _tile_kinds(sq_p, skv_p, skv, tq, tk, causal))


# (sq, skv, block_q, block_k, sub_q, sub_k) -> what causal_tile_plan says
PLAN_GEOMETRIES = {
    # the training cell: 4 x 4 sub-tiles of 256, 6 below the diagonal, 4 on
    # it, 6 above
    (1024, 1024, 1024, 1024, 256, 256): (6, 4, 6),
    (1024, 1024, 1024, 1024, 512, 512): (1, 2, 1),
    (1024, 1024, 1024, 1024, 128, 128): (28, 8, 28),
    (1024, 1024, 1024, 1024, 1024, 1024): (0, 1, 0),
    # a grid of several blocks: the sub-tile is the block
    (1024, 1024, 512, 512, 256, 256): (1, 2, 1),
    (4096, 4096, 1024, 1024, 256, 256): (6, 4, 6),
    (1100, 1100, 1024, 1024, 256, 256): (1, 2, 1),   # the last key block pads
    (200, 200, 1024, 1024, 256, 256): (0, 1, 0),     # no sub-tile divides 200
    (64, 64, 64, 64, 16, 16): (6, 4, 6),
}


@pytest.mark.parametrize("geometry", sorted(PLAN_GEOMETRIES),
                         ids=lambda g: "x".join(map(str, g)))
def test_causal_tile_plan_matches_the_mask(geometry):
    """The plan's three counts against the brute-force reading at the
    sub-tile the kernels use there, and against the table above."""
    from tnn_tpu.ops.pallas import flash_attention as fa

    sq, skv, block_q, block_k, sub_q, sub_k = geometry
    bq, bk, sq_p, skv_p = fa._block_geometry(sq, skv, block_q, block_k)
    tq, tk = fa._sub_tiles(bq, bk, sub_q, sub_k, (sq_p, skv_p) == (bq, bk))
    for causal in (True, False):
        want = _tile_kinds(sq_p, skv_p, skv, tq, tk, causal)
        plan = fa.causal_tile_plan(*geometry, causal=causal)
        assert plan == tuple(int((want == kind).sum()) for kind in (0, 1, 2))
        assert sum(plan) == want.size
    assert fa.causal_tile_plan(*geometry) == PLAN_GEOMETRIES[geometry]


class TestGQA:
    """Grouped-query attention (beyond reference): H_kv < H shares kv heads
    across query groups; the pallas kernel maps q-head grid indices to kv
    heads in its BlockSpecs (zero materialization)."""

    def _qkv(self, hq=4, hkv=2, sq=128, skv=128, d=32):
        rs = np.random.RandomState(21)
        q = jnp.asarray(rs.randn(2, hq, sq, d), jnp.float32)
        k = jnp.asarray(rs.randn(2, hkv, skv, d), jnp.float32)
        v = jnp.asarray(rs.randn(2, hkv, skv, d), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("hkv", [2, 1])  # grouped and MQA (single kv head)
    def test_flash_gqa_matches_repeated_kv(self, hkv):
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv(hkv=hkv)
        out = flash_attention(q, k, v, True, None, 64, 64)
        g = 4 // hkv
        ref = flash_attention(q, jnp.repeat(k, g, axis=1),
                              jnp.repeat(v, g, axis=1), True, None, 64, 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_xla_gqa_matches_repeated_kv(self):
        q, k, v = self._qkv()
        out = sdpa(q, k, v, causal=True, backend="xla")
        ref = sdpa(q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
                   causal=True, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("fused", ["1", "0"])
    def test_gqa_grads_match_repeated_kv(self, monkeypatch, fused):
        """dK/dV for a shared kv head must equal the SUM of its group's
        per-head grads — both fused and split backward paths."""
        from tnn_tpu.ops.pallas.flash_attention import flash_attention

        monkeypatch.setenv("TNN_FLASH_FUSED_BWD", fused)
        q, k, v = self._qkv()
        g = jnp.asarray(np.random.RandomState(3).randn(*q.shape), jnp.float32)

        def loss(q, k, v):
            return jnp.vdot(flash_attention(q, k, v, True, None, 64, 64,
                                            64, 64), g)

        def loss_rep(q, k2, v2):
            return jnp.vdot(flash_attention(q, jnp.repeat(k2, 2, axis=1),
                                            jnp.repeat(v2, 2, axis=1),
                                            True, None, 64, 64, 64, 64), g)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("dq dk dv".split(), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_mha_gqa_cached_decode_matches_full(self, rng, backend):
        mha = nn.MultiHeadAttention(num_heads=4, num_kv_heads=2, causal=True,
                                    backend=backend, policy=F32)
        x = jnp.asarray(np.random.RandomState(5).randn(2, 8, 32), jnp.float32)
        v = mha.init(rng, x.shape)
        full = mha(v, x)
        cache = mha.init_cache(2, 8, 32)
        assert cache["k"].shape == (2, 2, 8, 8)  # H_kv=2 sized cache
        out, cache = mha.apply_cached(v, x[:, :5], cache, 0)
        outs = [out]
        for t in range(5, 8):
            o, cache = mha.apply_cached(v, x[:, t:t + 1], cache, t)
            outs.append(o)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                                   np.asarray(full), rtol=1e-4, atol=1e-5)

    def test_gqa_config_roundtrip(self, rng):
        from tnn_tpu.core.module import module_from_config

        mha = nn.MultiHeadAttention(num_heads=6, num_kv_heads=3, causal=True)
        m2 = module_from_config(mha.get_config())
        assert m2.num_kv_heads == 3 and m2.num_heads == 6

    def test_bad_head_ratio_raises(self):
        with pytest.raises(ValueError):
            nn.MultiHeadAttention(num_heads=6, num_kv_heads=4)


def test_gqa_ulysses_indivisible_kv_heads_raises():
    """GQA + ulysses with H_kv not divisible by the shard count must fail
    loudly (the kv head all-to-all cannot split), not silently attend within
    each seq shard. Divisible H_kv proceeds; the ring method is always
    GQA-aware (test_parallel.test_ring_attention_gqa_matches_local)."""
    from tnn_tpu import parallel
    from tnn_tpu.nn import attention as attn_mod

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 4, 16, 8), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 16, 8), jnp.float32)
    mesh = parallel.make_mesh(seq=4)  # 2 kv heads cannot split over 4
    attn_mod._RING_CTX["mesh"] = mesh
    prev = attn_mod._RING_CTX.get("method")
    attn_mod._RING_CTX["method"] = "ulysses"
    try:
        with pytest.raises(ValueError, match="kv heads"):
            sdpa(q, k, k, causal=True)
    finally:
        attn_mod._RING_CTX["mesh"] = None
        attn_mod._RING_CTX["method"] = prev


def test_gqa_ulysses_divisible_kv_heads_matches_local():
    """H_kv % shards == 0: the ulysses kv all-to-all splits fine — verify
    against the local GQA kernels."""
    from tnn_tpu import parallel

    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(1, 4, 32, 8), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 32, 8), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 32, 8), jnp.float32)
    mesh = parallel.make_mesh(seq=2)
    ref = sdpa(q, k, v, causal=True)
    out = parallel.ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


class TestInt8KVCache:
    """kv_cache_dtype='int8': per-row symmetric int8 cache halves decode
    cache residency/traffic (composes with GQA)."""

    def test_cached_decode_close_to_full(self, rng):
        mha = nn.MultiHeadAttention(num_heads=4, causal=True,
                                    kv_cache_dtype="int8", policy=F32)
        x = jnp.asarray(np.random.RandomState(9).randn(2, 8, 32), jnp.float32)
        v = mha.init(rng, x.shape)
        full = mha(v, x)
        cache = mha.init_cache(2, 8, 32)
        assert cache["k"].dtype == jnp.int8
        assert cache["k_scale"].shape == (2, 4, 8, 1)
        out, cache = mha.apply_cached(v, x[:, :5], cache, 0)
        outs = [out]
        for t in range(5, 8):
            o, cache = mha.apply_cached(v, x[:, t:t + 1], cache, t)
            outs.append(o)
        got = np.asarray(jnp.concatenate(outs, axis=1))
        # int8 KV quantization noise: ~0.4% relative per row; attention keeps
        # it near that level. This is a closeness check, not bit-exactness.
        err = np.max(np.abs(got - np.asarray(full))) / max(
            1e-6, float(np.max(np.abs(np.asarray(full)))))
        assert err < 0.03, f"int8 cache decode rel err {err}"

    def test_cache_bytes_halved(self, rng):
        full = nn.MultiHeadAttention(num_heads=4, causal=True, policy=F32)
        q8 = nn.MultiHeadAttention(num_heads=4, causal=True,
                                   kv_cache_dtype="int8", policy=F32)
        c_full = full.init_cache(1, 128, 64)
        c_q8 = q8.init_cache(1, 128, 64)
        nb = lambda c: sum(np.asarray(v).nbytes for v in c.values())  # noqa: E731
        # f32 policy cache = 2*S*dh*4B; int8 = 2*S*(dh + 4)B
        assert nb(c_q8) < 0.4 * nb(c_full)

    def test_gpt2_generate_with_int8_cache(self):
        from tnn_tpu.models.gpt2 import GPT2, generate

        m = GPT2(vocab_size=96, max_len=32, num_layers=2, d_model=32,
                 num_heads=4, kv_cache_dtype="int8")
        variables = m.init(jax.random.PRNGKey(0), (1, 8))
        toks = generate(m, variables["params"],
                        jnp.asarray([[1, 2, 3]], jnp.int32), 5)
        assert toks.shape == (1, 5)  # generate returns the NEW tokens

    def test_config_roundtrip(self):
        from tnn_tpu.core.module import module_from_config
        from tnn_tpu.models.gpt2 import GPT2

        m = GPT2(vocab_size=96, max_len=32, num_layers=1, d_model=32,
                 num_heads=4, kv_cache_dtype="int8")
        m2 = module_from_config(m.get_config())
        assert m2.kv_cache_dtype == "int8"
        assert m2.blocks[0].attn.kv_cache_dtype == "int8"

    def test_bad_dtype_raises(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            nn.MultiHeadAttention(num_heads=2, kv_cache_dtype="int4")
