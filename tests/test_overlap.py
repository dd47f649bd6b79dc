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


@pytest.fixture
def fast_ramp(monkeypatch):
    """The ramp is a step an adoption and the time limit is out of the way,
    so a dozen steps are enough to queue deep."""
    from tnn_tpu.serving import engine

    monkeypatch.setattr(engine, "SPECULATE_RAMP", 1)
    monkeypatch.setattr(engine, "SPECULATE_AHEAD_S", 3600.0)


@pytest.mark.usefixtures("fast_ramp")
class TestSpeculationDepth:
    """A batch that has been closed for a while queues several steps ahead
    (``engine.SPECULATE_*``): a host that stands still for a moment then
    leaves the device busy. Here the ramp is a step an adoption and the
    time limit is out of the way, so a dozen steps are enough to go deep."""

    KW = dict(num_blocks=64, block_size=4, max_batch_size=4, max_seq_len=64)

    @staticmethod
    def _turn(eng):
        """One turn of the overlapped drive loop; how deep it queued."""
        if eng.in_flight is None:
            eng.begin_step()
        while eng.try_speculate():
            pass
        depth = len(eng.in_flight.ahead)
        eng.run_deferred()
        eng.finish_step()
        return depth

    @pytest.mark.parametrize("family,temperature", [
        ("gpt2", 0.0), ("gpt2", 0.8), ("llama", 0.8), ("mistral", 0.0)])
    def test_deep_chain_is_exact(self, lm, family, temperature):
        """Rows of three lengths decode 40 tokens each: the chain reaches
        its cap, shortens as the rows near their last token, and serves
        the synchronous loop's tokens (sampled ones too: every step draws
        the key it would have drawn)."""
        from tnn_tpu.serving import engine

        model, params = lm
        kw = dict(self.KW, prefix_cache=False)

        def submit(eng):
            return [eng.submit(p, 40, temperature=temperature)
                    for p in _prompts()]

        sync = InferenceEngine(model, params, **kw, overlap=False)
        rids = submit(sync)
        want = sync.run_until_complete()
        eng = InferenceEngine(model, params, **kw, overlap=True)
        assert submit(eng) == rids
        depths = []
        while eng.has_work or eng.in_flight is not None:
            depths.append(self._turn(eng))
        assert max(depths) == engine.SPECULATE_MAX
        assert depths[-1] == 0, "a step was queued behind a row's last"
        assert {r: list(eng.requests[r].out_tokens) for r in rids} == want
        assert eng.metrics.overlap_rebuilds == 0
        assert eng.pool.num_allocated == 0 and eng.in_flight is None

    @pytest.mark.parametrize("family", ["gpt2", "mistral"])
    def test_an_arrival_rolls_the_whole_chain_back(self, lm, family):
        """An arrival while five steps are queued: all are rolled back,
        their blocks freed and their keys drawn again in order, so every
        request (sampled at temperature) reads as in the synchronous loop
        with the same arrival."""
        model, params = lm
        kw = dict(self.KW, prefix_cache=False)
        prompts = _prompts()

        sync = InferenceEngine(model, params, **kw, overlap=False)
        rids = [sync.submit(p, 30, temperature=0.7) for p in prompts[:2]]
        for _ in range(11):
            sync.step()
        rids.append(sync.submit(prompts[2], 30, temperature=0.7))
        want = sync.run_until_complete()

        eng = InferenceEngine(model, params, **kw, overlap=True)
        assert [eng.submit(p, 30, temperature=0.7)
                for p in prompts[:2]] == rids[:2]
        for _ in range(10):
            self._turn(eng)
        if eng.in_flight is None:
            eng.begin_step()
        while eng.try_speculate():
            pass
        assert len(eng.in_flight.ahead) >= 5
        held = eng.pool.num_allocated
        assert eng.submit(prompts[2], 30, temperature=0.7) == rids[2]
        eng.finish_step()
        assert eng.metrics.overlap_rebuilds == 1 and eng.in_flight is None
        assert eng.pool.num_allocated <= held
        assert len(eng._reuse_keys) >= 5
        got = eng.run_until_complete()
        assert got == want
        assert eng.pool.num_allocated == 0 and not eng._reuse_keys

    def test_the_chain_deepens_a_step_at_a_time(self, tiny_lm, monkeypatch):
        """With the ramp at 4 adoptions a step and a time limit of three
        of the last step's lengths, the depth reads 1, 1, 1, 1, 2, ... and
        stops at 3."""
        from tnn_tpu.serving import engine

        monkeypatch.setattr(engine, "SPECULATE_RAMP", 4)
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        eng.submit(_prompts()[0], 40)
        # the prompt's mixed step: the first decode step goes out behind it
        depths = [self._turn(eng)]
        for _ in range(16):
            monkeypatch.setattr(engine, "SPECULATE_AHEAD_S",
                                2.5 * eng._last_step_latency_s)
            depths.append(self._turn(eng))
        assert depths[:9] == [1, 1, 1, 1, 2, 2, 2, 2, 3]
        assert max(depths) == 3


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


@pytest.mark.usefixtures("fast_ramp")
class TestLoadedServer:
    """A server under load has a queue, and its rows are taken: a request
    that WAITS changes nothing about the next step, so the chain of steps
    queued ahead goes on over it (``Scheduler.would_admit``), and every
    step is still the step the synchronous loop runs: same rows, same
    tokens, the head of the queue admitted at the same step."""

    KW = dict(num_blocks=64, block_size=4, max_batch_size=2, max_seq_len=64,
              prefix_cache=False)
    _turn = staticmethod(TestSpeculationDepth._turn)

    @staticmethod
    def _load(eng, temperature=0.0):
        """Five requests over two rows, outputs of five lengths: three wait,
        and every row's end lets the head of the queue in."""
        rng = np.random.default_rng(4)
        return [eng.submit(rng.integers(0, 128, p).astype(np.int32), n,
                           temperature=temperature)
                for p, n in ((5, 14), (9, 22), (7, 9), (12, 17), (6, 12))]

    @staticmethod
    def _sync(eng, before_step=None):
        """The synchronous loop, a flight record and the events a step."""
        log = []
        while eng.has_work:
            if before_step is not None:
                before_step(len(log))
            events = eng.step()
            log.append((eng.last_finished_record(), events))
        return log

    def _overlapped(self, eng, in_flight=None):
        """The overlapped loop; ``in_flight(i)`` runs while step i is."""
        log, depths = [], []
        while eng.has_work or eng.in_flight is not None:
            if eng.in_flight is None:
                eng.begin_step()
            while eng.try_speculate():
                pass
            depths.append(len(eng.in_flight.ahead))
            eng.run_deferred()
            if in_flight is not None:
                in_flight(len(log))
            events = eng.finish_step()
            log.append((eng.last_finished_record(), events))
        eng.run_deferred()
        return log, depths

    @staticmethod
    def _rows(log):
        return [(rec["running_rids"],
                 [p["kind"] for p in rec["programs"]]) for rec, _ in log]

    @staticmethod
    def _tokens(eng, rids):
        return {r: list(eng.requests[r].out_tokens) for r in rids}

    @pytest.mark.parametrize("family,temperature", [
        ("gpt2", 0.0), ("gpt2", 0.8), ("llama", 0.0), ("llama", 0.8),
        ("mistral", 0.0), ("mistral", 0.8)])
    def test_a_chain_is_adopted_over_a_queue(self, lm, family, temperature):
        model, params = lm
        sync = InferenceEngine(model, params, **self.KW, overlap=False)
        rids = self._load(sync, temperature)
        want = self._sync(sync)
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        assert self._load(eng, temperature) == rids
        got, depths = self._overlapped(eng)
        assert self._tokens(eng, rids) == self._tokens(sync, rids)
        # every step holds the rows it holds in the synchronous loop: the
        # head of the queue came in at the same step
        assert self._rows(got) == self._rows(want)
        over_a_queue = [rec for rec, _ in got
                        if rec.get("speculative") and rec["queued"]]
        assert len(over_a_queue) >= 10, "no chain went on over the queue"
        assert max(depths) >= 4
        # a row's last step is known beforehand: nothing is queued behind
        # it, so nothing is ever rolled back
        m = eng.metrics
        assert m.overlap_rebuilds == 0
        assert m.adopted_steps == sum(
            1 for rec, _ in got if rec.get("speculative"))
        assert m.summary()["adopted_step_share"] == pytest.approx(
            m.adopted_steps / len(got))
        assert m.summary()["adopted_step_share"] > 0.5
        assert eng.pool.num_allocated == 0 and not eng._reuse_keys

    def test_an_arrival_into_a_full_batch_rolls_nothing_back(self, tiny_lm):
        model, params = tiny_lm
        prompts = _prompts()

        def arrive(eng, at):
            def hook(i):
                if i == at:
                    hook.rid = eng.submit(prompts[2], 12, temperature=0.7)
            return hook

        sync = InferenceEngine(model, params, **self.KW, overlap=False)
        rids = [sync.submit(p, 30, temperature=0.7) for p in prompts[:2]]
        h = arrive(sync, 9)
        want = self._sync(sync, before_step=h)
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        assert [eng.submit(p, 30, temperature=0.7)
                for p in prompts[:2]] == rids
        # ... while step 8 is in flight with a chain behind it
        h2 = arrive(eng, 8)
        got, depths = self._overlapped(eng, in_flight=h2)
        assert depths[8] >= 5 and h2.rid == h.rid
        assert eng.metrics.overlap_rebuilds == 0
        assert got[9][0].get("speculative") and got[9][0]["queued"] == 1
        assert self._tokens(eng, rids + [h.rid]) == \
            self._tokens(sync, rids + [h.rid])
        assert self._rows(got) == self._rows(want)

    @pytest.mark.parametrize("limit,clock", [
        ("max_queue_s", "queued_time"), ("deadline_s", "submit_time")])
    def test_a_queued_request_times_out_at_its_step(self, tiny_lm, limit,
                                                    clock):
        """An adopted step never runs ``begin_step``: the queued requests'
        deadlines run at its adoption, into its events. The limit passes
        while step 6 runs; step 7 reports it in both loops, and the request
        behind it comes in when it would have."""
        model, params = tiny_lm

        def load(eng):
            rids = self._load(eng)
            # the head of the queue and the one behind it may time out
            for rid in rids[2:4]:
                setattr(eng.requests[rid], limit, 5e3)
            return rids

        def expire(eng, rids, at):
            def hook(i):
                if i == at:
                    req = eng.requests[rids[2]]
                    setattr(req, clock, getattr(req, clock) - 1e4)
            return hook

        sync = InferenceEngine(model, params, **self.KW, overlap=False)
        rids = load(sync)
        # the synchronous step 6 has returned; the overlapped one is flying
        want = self._sync(sync, before_step=expire(sync, rids, 7))
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        assert load(eng) == rids
        got, _ = self._overlapped(eng, in_flight=expire(eng, rids, 6))
        timed = [i for i, (_, ev) in enumerate(got) if ev["timed_out"]]
        assert timed == [7] == [i for i, (_, ev) in enumerate(want)
                                if ev["timed_out"]]
        assert got[7][1]["timed_out"] == want[7][1]["timed_out"]
        assert got[7][1]["timed_out"][0][0] == rids[2]
        assert got[7][0].get("speculative"), "step 7 was built, not adopted"
        assert eng.metrics.overlap_rebuilds == 0
        assert eng.requests[rids[2]].state.name == "TIMED_OUT"
        assert self._tokens(eng, rids) == self._tokens(sync, rids)
        assert self._rows(got) == self._rows(want)

    TIGHT = dict(KW, max_batch_size=3)

    @staticmethod
    def _tight_work():
        """Prompts of 20 and 22 tokens (5 and 6 blocks of four, 8 and 11 at
        their last token), then one of 30 (8 blocks at once), a short one
        and a later arrival: a pool of 17 to 18 blocks has a free ROW for
        the third all along and no room until the first ends."""
        rng = np.random.default_rng(8)
        return [(rng.integers(0, 128, p).astype(np.int32), n)
                for p, n in ((20, 12), (22, 22), (30, 8), (5, 6), (9, 6))]

    @pytest.mark.parametrize("num_blocks", [18, 19])
    def test_a_free_row_and_a_head_the_pool_cannot_hold(self, tiny_lm,
                                                        num_blocks):
        """Three rows, two taken, and a head of the queue whose prompt the
        pool has no room for: the chain goes on beside the free row (the
        scheduler's own arithmetic says the head does not fit), the head
        comes in at the synchronous loop's step, and nobody is preempted
        who is not there."""
        model, params = tiny_lm
        kw = dict(self.TIGHT, num_blocks=num_blocks)

        def load(eng):
            return [eng.submit(p, n, temperature=0.6)
                    for p, n in self._tight_work()[:4]]

        sync = InferenceEngine(model, params, **kw, overlap=False)
        rids = load(sync)
        want = self._sync(sync)
        eng = InferenceEngine(model, params, **kw, overlap=True)
        assert load(eng) == rids
        got, _ = self._overlapped(eng)
        assert self._rows(got) == self._rows(want)
        assert self._tokens(eng, rids) == self._tokens(sync, rids)
        assert eng.metrics.preemptions == sync.metrics.preemptions
        beside_a_free_row = [rec for rec, _ in got if rec.get("speculative")
                             and rec["queued"]
                             and len(rec["running_rids"]) < 3]
        assert len(beside_a_free_row) >= 5, "the head fitted: nothing shown"
        assert eng.pool.num_allocated == 0

    @pytest.mark.parametrize("num_blocks,at", [
        (17, 3), (18, 4), (18, 5), (19, 5), (19, 6), (19, 7)])
    def test_blocks_taken_ahead_are_free_to_an_arrival(self, tiny_lm,
                                                       num_blocks, at):
        """An arrival finds a free row and a pool whose last blocks the
        chain has taken for its rows' next tokens. The synchronous loop
        plans a step before its rows grow and admits the arrival; so does
        this one (``InferenceEngine._grown_ahead``): it rolls the chain
        back and admits at that step, sampled rows and all."""
        model, params = tiny_lm
        kw = dict(self.TIGHT, num_blocks=num_blocks)
        work = self._tight_work()

        def load(eng):
            return [eng.submit(p, n, temperature=0.6) for p, n in work[:2]]

        def arrive(eng, at):
            def hook(i):
                if i == at:
                    hook.rid = eng.submit(*work[4], temperature=0.6)
            return hook

        sync = InferenceEngine(model, params, **kw, overlap=False)
        rids = load(sync)
        h = arrive(sync, at + 1)
        want = self._sync(sync, before_step=h)
        eng = InferenceEngine(model, params, **kw, overlap=True)
        assert load(eng) == rids
        h2 = arrive(eng, at)
        got, _ = self._overlapped(eng, in_flight=h2)
        assert h2.rid == h.rid
        assert self._rows(got) == self._rows(want)
        assert self._tokens(eng, rids + [h.rid]) == \
            self._tokens(sync, rids + [h.rid])
        assert eng.metrics.preemptions == sync.metrics.preemptions
        assert eng.metrics.overlap_rebuilds == 1
        assert eng.pool.num_allocated == 0

    def test_each_refusal_counts_under_its_own_name(self, tiny_lm):
        """A step in flight and depth to spare, and no step dispatched:
        ONE reason each time, by name. A full batch with a queue behind it
        is no reason: the chain goes on."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        said = eng.metrics.speculate_refusals
        assert sorted(said) == ["admission", "mixed_step", "other", "pool",
                                "row_condition", "row_ends"]
        assert not eng.try_speculate() and not any(said.values())
        a = eng.submit(_prompts()[0], 20)
        eng.begin_step()                            # the prompt's chunk
        assert eng.try_speculate()                  # a plain chunk: no reason
        assert sum(said.values()) == 0
        eng.finish_step()                           # a decode step, adopted
        b = eng.submit(_prompts()[1], 30)           # a row is free for it
        assert not eng.try_speculate()
        assert said["admission"] == 1 and sum(said.values()) == 1
        eng.finish_step()
        c = eng.submit(_prompts()[2], 6)            # the batch is full: waits
        assert self._turn(eng) == 1                 # b's chunk beside a
        assert sum(said.values()) == 1
        for _ in range(4):
            assert self._turn(eng) >= 1             # ... over the queue
        assert sum(said.values()) == 1              # as deep as it may: none
        while eng.requests[a].state.name == "RUNNING":
            self._turn(eng)
        # the chain stopped short of a's last token, once a turn
        assert said["row_ends"] >= 1
        assert sum(said.values()) == 1 + said["row_ends"]
        eng.run_until_complete()
        s = eng.metrics.summary()
        assert s["speculate_refused_mixed_step"] == said["mixed_step"] == 0
        assert s["speculate_refused_row_ends"] == said["row_ends"]
        assert 0.0 < s["adopted_step_share"] < 1.0
        m = eng.metrics
        assert s["adopted_mixed_steps"] == 0 < s["adopted_decode_steps"] \
            == m.adopted_steps      # no prompt here is longer than a chunk
        fams = {f["name"]: f for f in eng.metrics.prometheus_series()}
        assert fams["tnn_serve_adopted_steps_total"]["samples"][0][-1] == \
            eng.metrics.adopted_steps
        assert fams["tnn_serve_adopted_decode_steps_total"]["samples"][0][
            -1] == m.adopted_steps
        by_reason = {lb["reason"]: v for _, lb, v in
                     fams["tnn_serve_speculate_refusals_total"]["samples"]}
        assert by_reason == {k: float(v) for k, v in said.items()}
        assert eng.requests[c].state.name == "FINISHED"

    def test_a_dry_pool_counts_as_pool(self, tiny_lm):
        """Two rows of 3 tokens hold a block of four each and the pool has
        one more: the step in flight writes position 3 (it went out behind
        the prompts' chunks), the next would need a block for each row,
        which the synchronous loop gets by a preemption and no prediction
        packs."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **dict(
            self.KW, num_blocks=4, max_seq_len=12), overlap=True)
        rids = [eng.submit(np.arange(3, dtype=np.int32) + i, 9)
                for i in range(2)]
        # the prompts' chunks; the step that writes position 3 goes out
        # behind them and is adopted
        assert self._turn(eng) == 1
        said = eng.metrics.speculate_refusals
        assert sum(said.values()) == 0
        assert eng.in_flight.note["speculative"]
        assert not eng.try_speculate()
        assert said["pool"] == 1 and sum(said.values()) == 1
        assert eng.metrics.preemptions == 0
        got = eng.run_until_complete()
        assert eng.metrics.preemptions >= 1
        sync = InferenceEngine(model, params, **dict(
            self.KW, num_blocks=4, max_seq_len=12), overlap=False)
        assert [sync.submit(np.arange(3, dtype=np.int32) + i, 9)
                for i in range(2)] == rids
        assert got == sync.run_until_complete()


@pytest.fixture(scope="module", params=["evabyte_tiny", "trinity_large_tiny"])
def windowed_lm(request):
    """A pool whose pages come and go: EVA's exact window of 32 beside
    chunk summaries, and sliding-window layers (a window of 16) beside
    global ones over two page groups."""
    from tnn_tpu import models
    from tnn_tpu.core.dtypes import DTypePolicy

    model = models.create(request.param, policy=DTypePolicy(
        io="float32", param="float32", compute="float32"))
    params = model.init(jax.random.PRNGKey(5), (1, 8))["params"]
    return model, params


@pytest.mark.usefixtures("fast_ramp")
class TestChainsThroughPrompts:
    """A step goes out behind a MIXED step too: a row pushing its prompt is
    its chunk on by the next step and takes the chunk the scheduler's own
    arithmetic grants there; its prompt done, it decodes from its first
    sample, still on the device. Chunks of four tokens make every prompt a
    run of mixed steps."""

    KW = dict(num_blocks=64, block_size=4, max_batch_size=4, max_seq_len=64,
              prefix_cache=False, chunk_size=4)
    _turn = staticmethod(TestSpeculationDepth._turn)
    _sync = staticmethod(TestLoadedServer._sync)
    _overlapped = TestLoadedServer._overlapped
    _rows = staticmethod(TestLoadedServer._rows)
    _tokens = staticmethod(TestLoadedServer._tokens)

    @staticmethod
    def _work(lengths, seed=6):
        rng = np.random.default_rng(seed)
        return [(rng.integers(0, 128, p).astype(np.int32), n)
                for p, n in lengths]

    def _both(self, lm, work, temperature=0.0, in_flight=None,
              before_step=None, **kw):
        """The same work through the synchronous loop and the overlapped
        one: (sync engine, its log, overlapped engine, its log, depths)."""
        model, params = lm
        kw = dict(self.KW, **kw)
        sync = InferenceEngine(model, params, **kw, overlap=False)
        rids = [sync.submit(p, n, temperature=temperature) for p, n in work]
        want = self._sync(sync, before_step=before_step and before_step(sync))
        eng = InferenceEngine(model, params, **kw, overlap=True)
        assert [eng.submit(p, n, temperature=temperature)
                for p, n in work] == rids
        got, depths = self._overlapped(eng, in_flight and in_flight(eng))
        return sync, want, eng, got, depths

    def _same(self, sync, want, eng, got):
        rids = sorted(sync.requests)
        assert sorted(eng.requests) == rids
        assert self._tokens(eng, rids) == self._tokens(sync, rids)
        assert [eng.requests[r].state for r in rids] == \
            [sync.requests[r].state for r in rids]
        assert self._rows(got) == self._rows(want)
        assert eng.pool.num_allocated == 0 and not eng._reuse_keys
        assert eng.in_flight is None

    @pytest.mark.parametrize("family,temperature", [
        ("gpt2", 0.0), ("gpt2", 0.8), ("llama", 0.0), ("llama", 0.8),
        ("mistral", 0.0), ("mistral", 0.8)])
    def test_a_chain_through_a_whole_prompt_is_adopted(self, lm, family,
                                                       temperature):
        """One prompt of 22 tokens in chunks of four: the admitting step is
        built, the five chunks behind it and every decode step are adopted,
        and the tokens (sampled ones too) are the synchronous loop's."""
        sync, want, eng, got, depths = self._both(
            lm, self._work([(22, 12)]), temperature)
        self._same(sync, want, eng, got)
        kinds = [(rec.get("speculative", False),
                  rec["programs"][0]["kind"]) for rec, _ in got]
        assert kinds == [(False, "mixed")] + [(True, "mixed")] * 5 \
            + [(True, "decode_paged")] * 11
        m = eng.metrics
        assert m.overlap_rebuilds == 0 and max(depths) >= 4
        s = m.summary()
        assert (s["adopted_mixed_steps"], s["adopted_decode_steps"]) == (5, 11)
        assert s["speculate_refused_mixed_step"] == 0
        fams = {f["name"]: f for f in m.prometheus_series()}
        assert fams["tnn_serve_adopted_mixed_steps_total"]["samples"][0][
            -1] == 5

    @pytest.mark.parametrize("family,temperature", [
        ("gpt2", 0.8), ("llama", 0.0), ("mistral", 0.8)])
    def test_a_row_moves_to_the_decode_rows_with_its_first_token(
            self, lm, family, temperature, monkeypatch):
        """Running order a, b, c with b still pushing its prompt: the step
        of b's last chunk holds rows (a, c, b), the one behind it (a, b,
        c), so b's first token is row 2 of the samples on the device and
        c's row 1: a gather, not the predecessor's order."""
        from tnn_tpu.serving import engine

        gathers = []
        real = engine._splice_prev_tokens

        def spy(toks, prev, idx, from_prev):
            gathers.append((toks.ndim, list(np.asarray(idx)),
                            list(np.asarray(from_prev))))
            return real(toks, prev, idx, from_prev)

        monkeypatch.setattr(engine, "_splice_prev_tokens", spy)
        sync, want, eng, got, _ = self._both(
            lm, self._work([(3, 30), (18, 12), (2, 30)]), temperature)
        self._same(sync, want, eng, got)
        assert eng.metrics.overlap_rebuilds == 0
        # b's chunks beside a and c decoding: their tokens from rows 0, 1
        assert (2, [0, 1, 2, 3], [True, True, False, False]) in gathers
        # ... and the decode step behind b's last chunk
        assert (1, [0, 2, 1, 3], [True, True, True, False]) in gathers
        moved = [rec for rec, _ in got if rec.get("speculative")
                 and rec["programs"][0]["kind"] == "decode_paged"]
        assert moved and eng.metrics.adopted_by_kind["mixed"] >= 3

    @pytest.mark.parametrize("family,temperature", [
        ("gpt2", 0.0), ("llama", 0.8), ("mistral", 0.0)])
    def test_two_rows_push_their_prompts_at_once(self, lm, family,
                                                 temperature):
        """Prompts of 19 and 11 tokens admitted into one step beside a row
        that decodes: both take a chunk a step, the shorter one ends first
        and decodes beside the longer one's last chunks."""
        sync, want, eng, got, _ = self._both(
            lm, self._work([(2, 26), (19, 8), (11, 9)]), temperature)
        self._same(sync, want, eng, got)
        m = eng.metrics
        assert m.overlap_rebuilds == 0
        assert m.speculate_refusals["mixed_step"] == 0
        two = [rec for rec, _ in got if rec.get("speculative")
               and rec["programs"][0]["kind"] == "mixed"]
        assert len(two) >= 4
        # built: the admitting step, and the step behind each row's end
        assert len(got) - m.adopted_steps <= 4

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_a_resumed_request_keeps_its_token(self, lm, family):
        """A pool of eight blocks preempts: the victim comes back with its
        output as more prompt and a token it already drew. The step behind
        its last chunk takes THAT token from the host, not the sample of
        the chunk (drawn at temperature: another token)."""
        from tnn_tpu.serving import engine

        kept = []

        def watch(eng):
            def hook(i):
                kept.extend(
                    row for s in eng.in_flight.ahead
                    for row in s["rec"]["before"]
                    if row.src == engine._ON_HOST and row.req.out_tokens
                    and row.cache_len == row.req.prefill_len)
            return hook

        sync, want, eng, got, _ = self._both(
            lm, self._work([(5, 10), (9, 10), (16, 10), (7, 10)], seed=1),
            temperature=0.7, in_flight=watch, num_blocks=9)
        assert eng.metrics.preemptions == sync.metrics.preemptions > 0
        self._same(sync, want, eng, got)
        assert kept, "no step went out behind a resumed row's last chunk"
        assert eng.metrics.overlap_rebuilds == 0

    @pytest.mark.parametrize("family", ["gpt2", "mistral"])
    def test_an_admitted_arrival_rolls_a_chain_of_chunks_back(self, lm,
                                                             family):
        """A row decodes, a prompt of 30 tokens is on its way in chunks
        with four steps queued, and a row is free: an arrival is admitted
        at the next step, so the chain is rolled back, the blocks taken
        for its chunks are freed and its keys drawn again in order."""
        held = {}

        def arrive(at):
            def make(eng):
                def hook(i):
                    if i == at:
                        ahead = eng.in_flight.ahead if eng.overlap else ()
                        held.update(
                            blocks=eng.pool.num_allocated, queued=len(ahead),
                            kinds=[s["rec"]["kind"] for s in ahead])
                        hook.rid = eng.submit(*self._work([(6, 9)], 3)[0],
                                              temperature=0.7)
                        resolve = eng._resolve_speculation

                        def resolved(flight):   # the next one only
                            del eng._resolve_speculation
                            resolve(flight)
                            held["after"] = eng.pool.num_allocated
                        eng._resolve_speculation = resolved
                return hook
            return make

        sync, want, eng, got, _ = self._both(
            lm, self._work([(3, 30), (30, 8)]), temperature=0.7,
            before_step=arrive(5), in_flight=arrive(4))
        self._same(sync, want, eng, got)
        # b's last chunks and the decode steps behind them
        assert held["queued"] >= 3 and held["kinds"][:2] == ["mixed"] * 2
        assert eng.metrics.overlap_rebuilds == 1
        # what the rows hold once the chain is gone: what they hold in the
        # synchronous loop after that step
        assert held["after"] == want[4][0]["pool_allocated"] < held["blocks"]
        assert not got[5][0].get("speculative")     # the admitting step

    def test_a_non_finite_chunk_fails_alone_and_the_chain_is_rebuilt(
            self, tiny_lm, monkeypatch):
        """The chunk that starts at position 8 of the second request comes
        back non-finite: that request fails, the steps queued behind the
        chunk are rolled back and built again without it, and the row
        beside it reads as in the synchronous loop."""
        from tnn_tpu.serving import step_build

        real = step_build.pack_mixed
        ahead_of_commit = []

        def poisoned(rows, n_dec, *a, **kw):
            step = real(rows, n_dec, *a, **kw)
            for i, req in enumerate(rows):
                if i >= n_dec and req.rid == 1 and step.starts[i] == 8:
                    step.poison[i] = np.nan
                    ahead_of_commit.append(kw.get("lens") is not None)
            return step

        monkeypatch.setattr(step_build, "pack_mixed", poisoned)
        sync, want, eng, got, _ = self._both(
            tiny_lm, self._work([(3, 24), (21, 8)]), temperature=0.7)
        assert ahead_of_commit == [False, True]
        self._same(sync, want, eng, got)
        assert eng.requests[1].state.name == "FAILED"
        assert "prefill chunk" in eng.requests[1].error
        assert eng.metrics.overlap_rebuilds == 1
        assert len(eng.requests[0].out_tokens) == 24

    def test_a_prompt_the_budget_leaves_no_chunk_counts_as_mixed_step(
            self, tiny_lm):
        """Three rows, then a token budget of five: a decode row and one
        chunk of four use it up, and the second prompt sits in its row with
        no chunk (a budget that shrinks under a row's feet: a window's end
        can do that to the rows behind it). What such a row gets next is
        not worked out ahead: ``mixed_step``, until the first prompt ends."""
        model, params = tiny_lm
        work = self._work([(2, 20), (14, 6), (10, 6)])

        def admitted(overlap):
            eng = InferenceEngine(model, params, **self.KW, overlap=overlap)
            rids = [eng.submit(p, n) for p, n in work]
            eng.step()
            assert len(eng.scheduler.running) == 3
            eng.scheduler.token_budget = 5
            return eng, rids

        sync, rids = admitted(False)
        want = self._sync(sync)
        eng, _ = admitted(True)
        got, _ = self._overlapped(eng)
        self._same(sync, want, eng, got)
        starved = [rec for rec, _ in got
                   if len(rec["programs"][0]["rids"]) == 2
                   and len(rec["running_rids"]) == 3]
        said = eng.metrics.speculate_refusals
        assert said["mixed_step"] == len(starved) >= 2
        assert not any(rec.get("speculative") for rec in starved)
        assert eng.metrics.overlap_rebuilds == 0
        assert eng.metrics.adopted_by_kind["mixed"] >= 1

    def test_a_row_that_left_ends_the_chain(self, tiny_lm):
        """A cancellation while steps are queued: the prediction behind
        them still holds the row, and nothing more goes out on it
        (``other``); the commit then rolls the chain back."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW, overlap=True)
        rids = [eng.submit(p, n) for p, n in
                self._work([(3, 30), (14, 30)])]
        for _ in range(2):
            self._turn(eng)
        if eng.in_flight is None:
            eng.begin_step()
        assert eng.try_speculate()                  # one queued, room for more
        assert eng.cancel(rids[0])
        held = eng.pool.num_allocated
        assert not eng.try_speculate()
        assert eng.metrics.speculate_refusals["other"] == 1
        assert eng.pool.num_allocated == held
        eng.finish_step()
        assert eng.metrics.overlap_rebuilds == 1 and eng.in_flight is None
        eng.run_until_complete()
        assert len(eng.requests[rids[1]].out_tokens) == 30
        assert eng.pool.num_allocated == 0

    def test_a_window_s_end_is_not_guessed(self, windowed_lm):
        """Prompts of 50 and 21 tokens in chunks of eight through a pool
        whose pages come and go: the chain stops where a chunk's commit
        ends a window or gives pages back (``row_condition``), goes on
        everywhere else, and serves the synchronous loop's tokens with
        every packed step held to the one-writer invariant."""
        model, _ = windowed_lm
        rng = np.random.default_rng(12)
        work = [(rng.integers(0, model.vocab_size, p).astype(np.int32), n)
                for p, n in ((50, 24), (21, 30))]
        sync, want, eng, got, _ = self._both(
            windowed_lm, work, num_blocks=128, block_size=8,
            max_seq_len=128, chunk_size=8)
        self._same(sync, want, eng, got)
        m = eng.metrics
        assert m.overlap_rebuilds == 0
        assert m.speculate_refusals["row_condition"] >= 2
        assert m.speculate_refusals["mixed_step"] == 0
        assert m.adopted_by_kind["mixed"] >= 2
        built = [rec for rec, _ in got if not rec.get("speculative")]
        assert len(built) < len(got) / 2
