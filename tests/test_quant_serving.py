"""Quantized serving path: int8 paged KV blocks (+ optional int8 weights).

The contract is CLOSENESS, not exactness: quantizing the KV pool changes
logits by rounding error, so int8 runs are gated on top-1 token agreement
against the offline float32 reference, ``models.gpt2.generate`` (measured
0.94-1.0 on the fixed-seed tiny model, gated at 0.8) — while everything *structural* stays exact: the pool's
block bookkeeping, zero-leak drain, COW privacy, and determinism of an
int8 engine against itself. The f32 engines' exactness matrix lives in
test_serving.py / test_overlap.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tnn_tpu.ops.pallas.paged_attention import (QuantPages, paged_attention,
                                                scatter_kv_chunk)
from tnn_tpu.serving import (TERMINAL_STATES, FaultPlan, InferenceEngine,
                             PagedKVPool, RequestState)
from tnn_tpu.serving import kv_pool as kv_pool_lib

KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, int(l)).astype(np.int32)
            for l in rng.integers(5, 14, n)]


def _run(model, params, prompts, max_new=8, stagger=0, **kw):
    merged = dict(KW)
    merged.update(kw)
    eng = InferenceEngine(model, params, **merged)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if stagger and i % stagger == stagger - 1:
            eng.step()
    out = eng.run_until_complete()
    return eng, [out[r] for r in rids]


def _agreement(a_runs, b_runs):
    """Fraction of positions where two engines emitted the same token."""
    match = total = 0
    for a, b in zip(a_runs, b_runs):
        assert len(a) == len(b)
        total += len(a)
        match += sum(int(x == y) for x, y in zip(a, b))
    return match / max(total, 1)


def _assert_drained(eng):
    states = {r.rid: r.state for r in eng.requests.values()}
    assert all(s in TERMINAL_STATES for s in states.values()), states
    assert not eng.has_work
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity
    eng.check_invariants()


# -- pool: int8 pages + scale sidecar lifecycle -------------------------------


class TestInt8Pool:
    def _pool(self, **kw):
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("head_dim", 8)
        kw.setdefault("num_blocks", 8)
        kw.setdefault("block_size", 4)
        kw.setdefault("kv_dtype", "int8")
        return PagedKVPool(**kw)

    def test_layout_and_byte_accounting(self):
        pool = self._pool(dtype=jnp.bfloat16)
        assert isinstance(pool.pages_k, QuantPages)
        assert pool.pages_k.data.dtype == jnp.int8
        assert pool.pages_k.scale.dtype == jnp.float32
        assert pool.pages_k.scale.shape == pool.pages_k.data.shape[:-1] + (1,)
        assert pool.page_itemsize == 1
        # K+V across layers, page arrays only: 2 * L * H_kv * Dh * 1 byte
        assert pool.kv_bytes_per_token == 2 * 2 * 2 * 8
        assert pool.kv_scale_bytes_per_token == 2 * 2 * 2 * 4
        # the acceptance ratio: a bf16 pool's pages are EXACTLY 2x int8's
        f32_pool = PagedKVPool(num_layers=2, num_kv_heads=2, head_dim=8,
                               num_blocks=8, block_size=4,
                               dtype=jnp.bfloat16)
        assert f32_pool.kv_bytes_per_token == 2 * pool.kv_bytes_per_token
        assert f32_pool.kv_scale_bytes_per_token == 0

    def test_lifecycle_and_invariants(self):
        """alloc/fork/free/truncate run unchanged on an int8 pool and the
        invariant checker verifies the scale sidecar stays in agreement."""
        pool = self._pool()
        blocks = pool.alloc(3)
        pool.check_invariants([blocks])
        forked = pool.fork(blocks)
        pool.check_invariants([blocks, forked])
        kept = pool.truncate(forked, 1)
        pool.check_invariants([blocks, kept])
        pool.free(kept)
        pool.free(blocks)
        pool.check_invariants([])
        # corrupt the bundle: a scale leaf of the wrong shape must be caught
        pool.pages_k = QuantPages(pool.pages_k.data,
                                  pool.pages_k.scale[..., 0])
        with pytest.raises(ValueError, match="scale"):
            pool.check_invariants([])

    def test_write_read_roundtrip(self):
        """Write-time quantization: the step's page write stores int8 rows
        with their scales, and the read the attention makes of them (the
        kernel's dequant: data times scale) is back within quantization
        error. The other layer's pages stay zero."""
        pool = self._pool()
        rng = np.random.default_rng(0)
        blocks = pool.alloc(2)
        table = jnp.asarray([pool.padded_table(blocks, 2)], jnp.int32)
        # (B, Q, H, Dh): 8 positions fill both pages
        kv = jnp.asarray(rng.normal(size=(1, 8, 2, 8)), jnp.float32)
        pool.pages_k = scatter_kv_chunk(
            pool.pages_k, table, jnp.asarray([0], jnp.int32), kv,
            jnp.asarray([8], jnp.int32), layer=1)
        assert pool.pages_k.data.dtype == jnp.int8
        deq = np.asarray(pool.pages_k.data, np.float32) \
            * np.asarray(pool.pages_k.scale)                # (L, N, H, bs, Dh)
        got = np.concatenate([deq[1, b] for b in blocks], axis=1)  # (H, 8, Dh)
        np.testing.assert_allclose(got.transpose(1, 0, 2),
                                   np.asarray(kv[0]), atol=3e-2)
        assert not deq[0].any()
        # and through the attention read itself: one query over the 8 keys
        # matches float32 attention over the rows that were written
        q = jnp.asarray(rng.normal(size=(1, 2, 8)), jnp.float32)
        pool.pages_v = scatter_kv_chunk(
            pool.pages_v, table, jnp.asarray([0], jnp.int32), kv,
            jnp.asarray([8], jnp.int32), layer=1)
        out = paged_attention(q, pool.pages_k, pool.pages_v, table,
                              jnp.asarray([8], jnp.int32), layer=1)
        k = np.asarray(kv[0]).transpose(1, 0, 2)                  # (H, 8, Dh)
        w = np.einsum("hd,htd->ht", np.asarray(q[0]), k) / np.sqrt(8)
        w = np.exp(w - w.max(-1, keepdims=True))
        want = np.einsum("ht,htd->hd", w / w.sum(-1, keepdims=True), k)
        np.testing.assert_allclose(np.asarray(out[0]), want, atol=5e-2)
        pool.free(blocks)

    def test_copy_blocks_and_reset_move_both_leaves(self):
        pool = self._pool()
        rng = np.random.default_rng(1)
        rows = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), jnp.float32)
        table = jnp.asarray([[2, 0]], jnp.int32)
        for layer in range(2):
            pool.pages_k = scatter_kv_chunk(
                pool.pages_k, table, jnp.asarray([1], jnp.int32), rows,
                jnp.asarray([1], jnp.int32), layer=layer)
        copied = kv_pool_lib.copy_blocks(pool.pages_k, [2], [5])
        np.testing.assert_array_equal(np.asarray(copied.data[:, 5]),
                                      np.asarray(pool.pages_k.data[:, 2]))
        np.testing.assert_array_equal(np.asarray(copied.scale[:, 5]),
                                      np.asarray(pool.pages_k.scale[:, 2]))
        pool.reset_pages()
        assert isinstance(pool.pages_k, QuantPages)
        assert not np.any(np.asarray(pool.pages_k.data))
        assert not np.any(np.asarray(pool.pages_k.scale))


def _refs(model, params, prompts, max_new=8):
    """The offline float32 reference: ``models.gpt2.generate`` over a
    contiguous cache of the engine's table width."""
    from tnn_tpu.models.gpt2 import generate

    return [np.asarray(generate(model, params, p[None], max_new,
                                max_len=KW["max_seq_len"]))[0].tolist()
            for p in prompts]


# -- engine: closeness gates, both model families -----------------------------


class TestInt8EngineCloseness:
    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_closeness_vs_f32(self, lm, family):
        """The quantization quality gate: int8-KV outputs agree with the
        float32 reference token-for-token at >= 0.8 (measured 0.94-1.0),
        drain with zero leaks, and report one byte a page element."""
        model, params = lm
        prompts = _prompts(4, seed=0)
        eng, out = _run(model, params, prompts, kv_dtype="int8")
        assert _agreement(out, _refs(model, params, prompts)) >= 0.8
        assert eng.stats()["kv_dtype"] == "int8"
        # one byte a page element: K and V, every layer, every KV head
        assert eng.stats()["kv_bytes_per_token"] == \
            2 * model.num_layers * model.num_kv_heads * eng.head_dim
        assert eng.stats()["kv_scale_bytes_per_token"] > 0
        _assert_drained(eng)

    @pytest.mark.parametrize(
        "family", ["gpt2", pytest.param("llama", marks=pytest.mark.slow)])
    def test_spec_prefix_overlap_compose(self, lm, family):
        """spec=ngram + prefix cache + overlapped loop all ride on int8
        blocks; the composed run stays close to the float32 reference and
        an int8 engine is deterministic against itself."""
        model, params = lm
        base = (np.arange(16) * 5 % 128).astype(np.int32)
        prompts = [base[:12], base[:9],
                   np.concatenate([base[:8], base[:4] + 1]).astype(np.int32)]
        kw = dict(spec="ngram", prefix_cache=True, overlap=True)
        eng, out = _run(model, params, prompts, kv_dtype="int8", **kw)
        _, out2 = _run(model, params, prompts, kv_dtype="int8", **kw)
        assert out == out2, "int8 engine is not deterministic"
        assert _agreement(out, _refs(model, params, prompts)) >= 0.8
        _assert_drained(eng)

    def test_quant_weights_compose(self, tiny_lm):
        model, params = tiny_lm
        prompts = _prompts(3, seed=2)
        eng, out = _run(model, params, prompts, kv_dtype="int8",
                        quant_weights=True)
        assert _agreement(out, _refs(model, params, prompts)) >= 0.8
        assert eng.stats()["quant_weights"]
        _assert_drained(eng)

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_cow_at_partial_block_boundary_int8(self, lm, family):
        """COW on quantized blocks: a full-cover prefix hit re-quantizes
        only its recomputed last token into a PRIVATE copy, so the twin is
        token-identical to the original (same int8 cache bytes, greedy) and
        the published blocks survive for the next twin."""
        model, params = lm
        p = np.arange(8, dtype=np.int32)   # exactly 2 full blocks
        eng = InferenceEngine(model, params, **KW, kv_dtype="int8")
        r0 = eng.submit(p, 8)
        ref = eng.run_until_complete()[r0]
        assert eng.metrics.prefix_cows == 0
        r1 = eng.submit(p, 8)
        assert eng.run_until_complete()[r1] == ref
        assert eng.metrics.prefix_cows == 1
        r2 = eng.submit(p, 8)
        assert eng.run_until_complete()[r2] == ref
        assert eng.metrics.prefix_cows == 2
        _assert_drained(eng)

    @pytest.mark.slow
    def test_chaos_gate_int8(self, tiny_lm):
        """The fault-tolerance gate on int8 blocks: alloc faults + a NaN
        row never leak a page OR its scale sidecar — every request reaches
        a terminal state, survivors match a fault-free int8 run exactly,
        and check_invariants (which audits the quantized bundle) is clean."""
        model, params = tiny_lm
        prompts = _prompts(8, seed=6)
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32, kv_dtype="int8")

        def run(plan=None):
            eng = InferenceEngine(model, params, faults=plan, **kw)
            rids = [eng.submit(p, 8) for p in prompts]
            eng.run_until_complete()
            return eng, rids

        ref_eng, ref_rids = run()
        plan = FaultPlan(seed=9, alloc_fail_prob=0.12, nan_logit_calls=(5,))
        eng, rids = run(plan)
        assert plan.fired["pool.alloc"] >= 1, "chaos never fired — dead test"
        states = [eng.result(r).state for r in rids]
        assert all(s in TERMINAL_STATES for s in states)
        for rid, ref_rid in zip(rids, ref_rids):
            if eng.result(rid).state is RequestState.FINISHED:
                assert list(eng.requests[rid].out_tokens) == \
                    list(ref_eng.requests[ref_rid].out_tokens)
        _assert_drained(eng)

    def test_gauges_and_exposition(self, tiny_lm):
        model, params = tiny_lm
        eng, _ = _run(model, params, _prompts(2, seed=3), kv_dtype="int8")
        fams = {f["name"]: f for f in eng.metrics.prometheus_series()}
        fam = fams["tnn_serve_kv_bytes_per_token"]
        assert fam["type"] == "gauge"
        assert fam["samples"][0][-1] == float(eng.pool.kv_bytes_per_token)
        assert eng.metrics.summary()["kv_bytes_per_token"] == \
            eng.pool.kv_bytes_per_token


# -- acceptance: gpt2_small closeness (slow lane) -----------------------------


@pytest.mark.slow
def test_gpt2_small_int8_closeness():
    """Closeness at depth: on gpt2_small, every int8-engine token must be
    the f32 teacher-forced argmax or within a near-tie margin of it — the
    same methodology as the f32 acceptance gate, with the margin widened to
    absorb int8 rounding (logit deltas ~1e-2 on this model)."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, model.vocab_size, (4, 12)).astype(np.int32)
    max_new = 12

    eng = InferenceEngine(model, params, num_blocks=14, block_size=16,
                          max_batch_size=4, max_seq_len=32, kv_dtype="int8")
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_until_complete()
    assert all(len(out[r]) == max_new for r in rids)
    assert eng.pool.num_allocated == 0

    seqs = np.stack([np.concatenate([prompts[i], out[rids[i]]])
                     for i in range(len(rids))])
    caches = model.init_cache(len(rids), seqs.shape[1])
    logits, _ = model.apply_cached(params, jnp.asarray(seqs), caches, 0)
    logits = np.asarray(logits, np.float64)
    plen = prompts.shape[1]
    exact, margins = 0, []
    for i in range(len(rids)):
        for j in range(max_new):
            row = logits[i, plen + j - 1]
            chosen = seqs[i, plen + j]
            if chosen == row.argmax():
                exact += 1
            else:
                margins.append(float(row.max() - row[chosen]))
    total = len(rids) * max_new
    assert exact >= 0.75 * total, f"only {exact}/{total} tokens were argmax"
    assert all(m < 0.25 for m in margins), f"beyond quant noise: {margins}"
