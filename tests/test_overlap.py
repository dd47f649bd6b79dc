"""Overlapped engine loop (PR: async overlap): the double-buffered loop is
token-exact against the synchronous loop for both model families, survives
staggered arrivals / preemption / mid-run crashes, never publishes prefix
blocks for a terminated request, and stays clean under TNN_DEBUG_SYNC=1.

The exactness matrix is the tentpole's hard invariant: overlap changes WHEN
host bookkeeping runs, never WHAT tokens come out. Heavy combinations ride
the documented `slow` lane; tier-1 keeps one representative per axis.
"""
import numpy as np
import pytest

import jax

from tnn_tpu.serving.engine import InferenceEngine
from tnn_tpu.serving.faults import FaultPlan
from tnn_tpu.serving.supervisor import EngineSupervisor

KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


@pytest.fixture(scope="module")
def draft_lm(tiny_lm):
    """Vocab-matched stand-in drafter (random weights: acceptance is poor,
    which exercises the reject/rollback arm of verification)."""
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(7), (1, 8))["params"]
    return model, params


def _prompts():
    # shared 8-token prefix so the prefix cache actually publishes+matches
    base = (np.arange(16) * 5 % 128).astype(np.int32)
    return [base[:12], base[:9], np.concatenate([base[:8],
                                                 base[:4] + 1]).astype(
                                                     np.int32)]


def _run(model, params, overlap, prompts=None, max_new=8, **kw):
    eng = InferenceEngine(model, params, **KW, overlap=overlap, **kw)
    rids = [eng.submit(p, max_new) for p in (prompts or _prompts())]
    out = eng.run_until_complete()
    return {r: out[r] for r in rids}, eng


class TestOverlapTokenExact:
    @pytest.mark.parametrize("family,spec", [
        ("gpt2", "off"),
        ("gpt2", "ngram"),
        ("llama", "off"),
        pytest.param("llama", "ngram", marks=pytest.mark.slow),
        pytest.param("llama", "draft", marks=pytest.mark.slow),
        pytest.param("gpt2", "draft", marks=pytest.mark.slow),
    ])
    def test_matrix(self, lm, draft_lm, family, spec):
        model, params = lm
        kw = dict(prefix_cache=True)
        if spec != "off":
            kw["spec"] = spec
        if spec == "draft":
            kw["draft_model"], kw["draft_params"] = draft_lm
        off, _ = _run(model, params, overlap=False, **kw)
        on, eng = _run(model, params, overlap=True, **kw)
        assert on == off, f"overlap changed tokens on {family}/{spec}"
        # the loop actually overlapped: the fetch->dispatch gap was measured
        assert len(eng.metrics.host_gap_s) > 0
        assert eng.in_flight is None and not eng._deferred

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_staggered_preempted_exact(self, lm, family):
        """Arrivals landing WHILE a step is in flight, on a pool small
        enough to preempt, still commit the synchronous loop's tokens."""
        model, params = lm
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        small = dict(KW, num_blocks=9)

        eng_off = InferenceEngine(model, params, **small, overlap=False)
        rids = [eng_off.submit(prompts[0], 10)]
        eng_off.step(); eng_off.step()
        rids += [eng_off.submit(p, 10) for p in prompts[1:]]
        off = eng_off.run_until_complete()

        eng = InferenceEngine(model, params, **small, overlap=True)
        rids = [eng.submit(prompts[0], 10)]
        eng.begin_step(); eng.finish_step()
        eng.begin_step()
        # mid-flight arrivals: scheduled at the next build, exactly like a
        # between-steps arrival in the synchronous loop
        rids += [eng.submit(p, 10) for p in prompts[1:]]
        eng.finish_step()
        on = eng.run_until_complete()
        assert eng.metrics.preemptions > 0, "pool was never exhausted"
        for rid in rids:
            assert on[rid] == off[rid]
        assert eng.pool.num_allocated == 0

    def test_crash_migration_exact(self, tiny_lm):
        """A mid-run engine crash under the supervisor recovers token-exact
        with overlap on, and the crash dump still ends with the dying step."""
        model, params = tiny_lm

        def run(overlap):
            eng = InferenceEngine(
                model, params, **KW, overlap=overlap,
                faults=FaultPlan(step_crash_calls=(3,)))
            sup = EngineSupervisor(eng, max_restarts=3)
            events = []
            rids = [sup.submit(p, 8, listener=events.append)
                    for p in _prompts()]
            sup.run_sync()
            terminals = [e for e in events
                         if e["event"] in ("done", "error", "timeout",
                                           "cancelled")]
            return ({r: list(eng.requests[r].out_tokens) for r in rids},
                    terminals, sup)

        off, term_off, _ = run(False)
        on, term_on, sup = run(True)
        assert on == off
        assert sup.restarts == 1
        assert len(term_on) == len(term_off) == len(_prompts())
        crashed = [r for r in sup.flight.records() if r.get("crashed")]
        assert len(crashed) == 1 and "EngineCrash" in crashed[0]["error"]


class TestSpeculativeSteps:
    def test_adoption_and_exactness(self, tiny_lm):
        """The idle-time speculative build fires on a steady decode batch
        and adopting it never changes tokens."""
        model, params = tiny_lm
        off, _ = _run(model, params, overlap=False)
        eng = InferenceEngine(model, params, **KW, overlap=True)
        rids = [eng.submit(p, 8) for p in _prompts()]
        adopted = 0
        while eng.has_work or eng.in_flight is not None:
            if eng.in_flight is None:
                eng.begin_step()
            eng.try_speculate()
            eng.run_deferred()
            eng.finish_step()
            if eng.in_flight is not None and \
                    eng._step_note.get("speculative"):
                adopted += 1
        eng.run_deferred()
        assert adopted > 0, "speculation never fired on a steady batch"
        assert {r: list(eng.requests[r].out_tokens) for r in rids} == off

    def test_mispredict_rolls_back(self, tiny_lm):
        """An arrival between dispatch and resolve invalidates the
        speculative step: it is rolled back (counted) and the rebuilt step
        commits the synchronous loop's tokens for everyone."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW, overlap=True)
        prompts = _prompts()
        rids = [eng.submit(p, 8) for p in prompts[:2]]
        # settle into steady decode so try_speculate's gate opens
        for _ in range(3):
            eng.begin_step(); eng.finish_step()
        eng.begin_step()
        assert eng.try_speculate(), "speculation gate unexpectedly closed"
        rids.append(eng.submit(prompts[2], 8))   # invalidates the prediction
        eng.finish_step()
        assert eng.metrics.overlap_rebuilds >= 1
        on = eng.run_until_complete()
        off, _ = _run(model, params, overlap=False)
        for rid, want in zip(rids, off.values()):
            assert on.get(rid, list(eng.requests[rid].out_tokens)) == want


class TestDeferredPhase:
    def test_publish_never_lands_for_terminated(self, tiny_lm):
        """A deferred prefix publish queued at commit is guarded at RUN
        time: cancelling the request before the deferred phase runs must
        drop the publish (its blocks are already freed)."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW, overlap=True,
                              prefix_cache=True)
        published = []
        real = eng.prefix_cache.publish
        eng.prefix_cache.publish = (
            lambda *a, **k: (published.append(a), real(*a, **k)))
        rid = eng.submit(_prompts()[0], 8)
        for _ in range(12):
            if eng.in_flight is None:
                eng.begin_step()
            eng.finish_step()          # commits defer publishes, not run yet
            if eng._deferred:
                break
        assert eng._deferred, "no deferred publish was queued"
        eng.cancel(rid, "test cancel")
        eng.run_deferred()
        assert published == [], "publish landed for a terminated request"
        # positive control: left alone, the publish lands
        rid2 = eng.submit(_prompts()[1], 8)
        eng.run_until_complete()
        assert published, "publish never landed for a live request"
        assert eng.requests[rid2].state.name == "FINISHED"

    def test_host_gap_observability(self, tiny_lm):
        """host_gap lands in the per-request breakdown, the metrics
        summary, and the Prometheus exposition."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW, overlap=True)
        sup = EngineSupervisor(eng)
        events = []
        sup.submit(_prompts()[0], 8, listener=events.append)
        sup.run_sync()
        done = [e for e in events if e["event"] == "done"]
        assert done and done[0]["latency_breakdown"]["host_gap_ms"] >= 0.0
        s = eng.metrics.summary()
        assert {"host_gap_ms_mean", "host_gap_ms_p50", "host_gap_ms_p99",
                "overlap_rebuilds"} <= set(s)
        fams = {f["name"] for f in eng.metrics.prometheus_series()}
        assert "tnn_serve_host_gap_seconds_total" in fams
        assert "tnn_serve_overlap_rebuilds_total" in fams
        # commit-time gauges: what /healthz now serves without engine access
        g = sup.health_gauges()
        assert g.pop("age_s") >= 0.0          # staleness of the snapshot
        assert g.pop("step_latency_s") > 0.0  # steps ran: last wall time
        assert g == {
            "queue_depth": 0, "num_running": 0, "kv_dtype": "f32",
            "kv_bytes_per_token": eng.pool.kv_bytes_per_token,
            "quant_weights": 0, "tp_degree": 1, "sp_degree": 1,
            "kv_bytes_per_token_per_shard": eng.pool.kv_bytes_per_token,
            "pool_blocks_per_shard": eng.pool.num_blocks,
            "host_tier_max_bytes": 0, "tier_blocks": 0}


class TestDebugSyncOverlap:
    def test_overlapped_twin_is_clean_and_exact(self, tiny_lm, monkeypatch):
        """jax.transfer_guard('disallow') over the whole overlapped loop:
        build, speculative dispatch, and the single bundle fetch are all
        explicit, so the guarded run neither raises nor diverges."""
        model, params = tiny_lm
        ref, _ = _run(model, params, overlap=True, spec="ngram")
        monkeypatch.setenv("TNN_DEBUG_SYNC", "1")
        got, eng = _run(model, params, overlap=True, spec="ngram")
        assert eng.debug_sync
        assert got == ref
