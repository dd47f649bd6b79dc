"""Tensor-parallel serving: tp=2 must be TOKEN-EXACT against tp=1.

Unlike the int8 lane (closeness-gated), TP changes nothing numerically
except the all-reduce order of two matmul partial sums per layer — on the
fixed-seed tiny model that drift never flips a sampled token, so the gate
here is byte-exactness: every composition that works at tp=1 (spec
decode, prefix cache, the overlapped loop, int8 KV) must emit
identical token streams at tp=2, through staggered arrivals, preemption,
and a mid-run supervisor crash (whose pool reset must purge EVERY shard).

Runs on the conftest's 8-device virtual CPU platform; the ``tp`` fixture
skips on real single-chip hosts.
"""
import numpy as np
import pytest

import jax

from tnn_tpu.serving import (TERMINAL_STATES, EngineSupervisor, FaultPlan,
                             InferenceEngine, RequestState)

pytestmark = pytest.mark.tp

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


def _greedy_ref(model, params, prompt, max_new, max_len):
    from tnn_tpu.models.gpt2 import generate

    return np.asarray(generate(model, params, prompt[None], max_new,
                               max_len=max_len))[0].tolist()


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


def _assert_drained(eng):
    states = {r.rid: r.state for r in eng.requests.values()}
    assert all(s in TERMINAL_STATES for s in states.values()), states
    assert not eng.has_work
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity
    eng.check_invariants()


def _shard_devices(eng):
    """The distinct devices actually holding the engine's KV pages."""
    pages = eng.pool.pages_k
    data = pages.data if hasattr(pages, "data") else pages
    return {d for d in data.sharding.device_set}


# -- fail-fast validation -----------------------------------------------------


class TestTPValidation:
    def test_rejects_indivisible_kv_heads(self, tp):
        from tnn_tpu.models.gpt2 import GPT2

        model = GPT2(vocab_size=128, max_len=64, num_layers=1, d_model=48,
                     num_heads=3)
        params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
        with pytest.raises(ValueError, match="divisible"):
            InferenceEngine(model, params, tp=tp, **KW)

    def test_rejects_tp_over_device_count(self, tiny_lm, tp):
        model, params = tiny_lm
        toomany = jax.device_count() + 1
        with pytest.raises(ValueError, match="device"):
            InferenceEngine(model, params, tp=toomany, **KW)

    def test_rejects_quant_weights(self, tiny_lm, tp):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="quant"):
            InferenceEngine(model, params, tp=tp, quant_weights=True, **KW)


# -- exactness: tp=2 == tp=1 == offline reference -----------------------------


class TestTPExactness:
    def test_staggered_parity(self, tiny_lm, tp):
        """Staggered admission (ragged offsets): tp=2 streams must equal
        tp=1 streams AND the offline greedy reference, token for token."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=5)
        kw = dict(stagger=2)
        eng1, base = _run(model, params, prompts, **kw)
        eng2, sharded = _run(model, params, prompts, tp=tp, **kw)
        assert sharded == base
        for toks, p in zip(sharded, prompts):
            assert toks == _greedy_ref(model, params, p, 8,
                                       eng2.assembly_len)
        assert eng2.stats()["tp_degree"] == tp
        assert len(_shard_devices(eng2)) == tp
        _assert_drained(eng2)

    def test_full_composition_exact(self, tiny_lm, tp):
        """The whole stack at once — int8 KV + ngram spec decode + prefix
        cache + overlapped loop on the paged path — must match the same
        composition at tp=1 exactly (int8 rounding is identical on every
        shard, so even the closeness-gated lane becomes parity here)."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=7) + _prompts(2, seed=7)[:1]  # a repeat
        kw = dict(kv_dtype="int8", spec="ngram",
                  prefix_cache=True, overlap=True)
        eng1, base = _run(model, params, prompts, **kw)
        eng2, sharded = _run(model, params, prompts, tp=tp, **kw)
        assert sharded == base
        assert eng2.stats()["kv_dtype"] == "int8"
        _assert_drained(eng2)

    def test_preemption_parity(self, tiny_lm, tp):
        """A starved pool preempts identically under TP: recompute-requeue
        produces byte-identical output and no shard leaks a block."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=1)
        kw = dict(num_blocks=9)
        eng1, base = _run(model, params, prompts, max_new=10, **kw)
        eng2, sharded = _run(model, params, prompts, max_new=10, tp=tp, **kw)
        assert eng2.metrics.preemptions > 0, "pool was never exhausted"
        assert sharded == base
        _assert_drained(eng2)

    def test_sampled_rows_deterministic(self, tiny_lm, tp):
        """Stochastic sampling inside the shard_map body: same seed, same
        tokens as tp=1 (the PRNG key replicates, threefry is elementwise,
        and the logits agree to the last ulp on this model)."""
        model, params = tiny_lm
        p = np.arange(6, dtype=np.int32)

        def run(**kw):
            eng = InferenceEngine(model, params, seed=3, **KW, **kw)
            g = eng.submit(p, 8)
            s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
            out = eng.run_until_complete()
            return eng, out[g], out[s]

        eng1, g1, s1 = run()
        eng2, g2, s2 = run(tp=tp)
        assert g2 == g1 == _greedy_ref(model, params, p, 8,
                                       eng2.assembly_len)
        assert s2 == s1
        assert all(0 <= t < model.vocab_size for t in s2)

    def test_debug_sync_clean(self, tiny_lm, tp, monkeypatch):
        """TNN_DEBUG_SYNC=1 (transfer guard around every step) must stay
        clean under TP: replication onto the mesh is an EXPLICIT device_put,
        never an implicit host round-trip."""
        monkeypatch.setenv("TNN_DEBUG_SYNC", "1")
        model, params = tiny_lm
        prompts = _prompts(3, seed=2)
        eng, out = _run(model, params, prompts, tp=tp,
                        spec="ngram", overlap=True)
        for toks, p in zip(out, prompts):
            assert toks == _greedy_ref(model, params, p, 8,
                                       eng.assembly_len)
        _assert_drained(eng)


# -- failure handling ---------------------------------------------------------


class TestTPFailures:
    def test_supervisor_crash_restart_exact(self, tiny_lm, tp):
        """A mid-run engine crash under TP: the supervisor's restart resets
        the pool — the reset must purge EVERY shard's pages (a stale shard
        would poison resumed attention silently) — and the migrated requests
        finish token-exact."""
        model, params = tiny_lm
        plan = FaultPlan(step_crash_calls=(2,))
        eng = InferenceEngine(model, params, tp=tp, faults=plan,
                              num_blocks=32,
                              block_size=4, max_batch_size=2, max_seq_len=32)
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               restart_backoff_s=0.0, max_restarts=2)
        prompts = _prompts(4, seed=9)
        refs = [_greedy_ref(model, params, p, 5, eng.assembly_len)
                for p in prompts]
        rids = [sup.submit(p, 5) for p in prompts]
        sup.run_sync()
        assert sup.restarts == 1
        term = {e["id"]: e for e in events if e["event"] != "token"}
        assert sorted(term) == sorted(rids)
        for rid, ref in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == ref
        # the reset pool is still head-sharded across all tp devices
        assert len(_shard_devices(eng)) == tp
        _assert_drained(eng)

    def test_chaos_gate_per_shard(self, tiny_lm, tp):
        """The existing chaos gate at tp=2: alloc faults + a NaN row leak
        zero blocks on any shard, survivors match a fault-free TP run."""
        model, params = tiny_lm
        prompts = _prompts(8, seed=6)
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32, tp=tp)

        def run(plan=None):
            eng = InferenceEngine(model, params, faults=plan, **kw)
            rids = [eng.submit(p, 8) for p in prompts]
            eng.run_until_complete()
            return eng, rids

        ref_eng, ref_rids = run()
        plan = FaultPlan(seed=9, alloc_fail_prob=0.12, nan_logit_calls=(5,))
        eng, rids = run(plan)
        assert plan.fired["pool.alloc"] >= 1, "chaos never fired — dead test"
        assert all(eng.result(r).state in TERMINAL_STATES for r in rids)
        for rid, ref_rid in zip(rids, ref_rids):
            if eng.result(rid).state is RequestState.FINISHED:
                assert list(eng.requests[rid].out_tokens) == \
                    list(ref_eng.requests[ref_rid].out_tokens)
        _assert_drained(eng)


# -- observability ------------------------------------------------------------


class TestTPObservability:
    def test_gauges_and_exposition(self, tiny_lm, tp):
        model, params = tiny_lm
        eng, _ = _run(model, params, _prompts(2, seed=3), tp=tp,
                      kv_dtype="int8")
        s = eng.stats()
        assert s["tp_degree"] == tp
        per_tok = eng.pool.kv_bytes_per_token + \
            eng.pool.kv_scale_bytes_per_token
        assert s["kv_bytes_per_token_per_shard"] == per_tok // tp
        fams = {f["name"]: f for f in eng.metrics.prometheus_series()}
        fam = fams["tnn_serve_tp_degree"]
        assert fam["type"] == "gauge"
        assert fam["samples"][0][-1] == float(tp)
        assert eng.metrics.summary()["tp_degree"] == tp

    def test_health_gauges_expose_tp(self, tiny_lm, tp):
        """The commit-time gauge snapshot (what /healthz serves without
        engine access) carries the TP degree and per-shard KV footprint."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, tp=tp, **KW)
        sup = EngineSupervisor(eng)
        sup.submit(_prompts(1, seed=4)[0], 6)
        sup.run_sync()
        g = sup.health_gauges()
        assert g["tp_degree"] == tp
        assert g["kv_bytes_per_token_per_shard"] == \
            (eng.pool.kv_bytes_per_token +
             eng.pool.kv_scale_bytes_per_token) // tp

    def test_allreduce_span_traced(self, tiny_lm, tp):
        """With tracing on, TP dispatch wraps the step in a serve.allreduce
        span carrying the degree and per-step all-reduce count."""
        from tnn_tpu.profiling.profiler import Profiler

        model, params = tiny_lm
        prof = Profiler(source="tp-test")
        eng, _ = _run(model, params, _prompts(2, seed=8), tp=tp,
                      profiler=prof, trace=True)
        spans = [e for e in prof.events
                 if e.name.startswith("serve.allreduce")]
        assert spans, "no serve.allreduce span recorded"
        assert f"tp={tp}" in spans[0].name
        assert f"count={2 * model.num_layers}" in spans[0].name
