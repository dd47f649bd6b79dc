"""End-to-end serving observability tests (PR 10).

Four contracts:

* Tracing is FREE of semantic effect: a traced engine (spans + instants
  flowing into a ``Profiler``) produces token-identical output to an
  untraced one, on both decode paths, with speculative decoding and the
  prefix cache on — and stays clean under ``TNN_DEBUG_SYNC=1`` (tracing
  is host-side bookkeeping, never a device sync).
* The crash flight recorder: a bounded ring of per-step records owned by
  the supervisor, dumped as JSONL on crash/drain; the LAST record of a
  crash dump identifies the crashing step's batch.
* ``ServingMetrics`` sample series are bounded (fixed-size reservoir) —
  a week-long serve must not grow per-request lists without bound.
* The Prometheus text exposition parses: HELP/TYPE headers, cumulative
  histogram buckets, labeled per-replica series through the Router.

PR 24 adds the second sink and the device-side names: under
``jax.profiler.start_trace`` the engine's phase spans and the training
spans land in the profile's host plane with their attributes as stats, and
the lowered step programs carry every scope and kernel name of
docs/observability.md's catalog.

PR 39 accounts for the step boundary: the spans it adds (``serve.put`` /
``serve.launch`` inside ``serve.dispatch``, ``serve.speculate`` round a step
dispatched ahead) nest and tile the overlapped worker's time, the counters
beside them are in ``summary()`` and ``EXPOSITION``, the front end's empty
polls say how late the machine let them be, and what a step pays for all of
it with no profiler session is measured here.
"""
import glob
import json
import os
import re
import statistics
import threading
import time
from unittest import mock

import numpy as np
import pytest

import jax

from tnn_tpu.profiling.profiler import Profiler
from tnn_tpu.serving import (EngineSupervisor, FaultPlan, InferenceEngine,
                             Router, ServingMetrics, SupervisorState,
                             render_prometheus)
from tnn_tpu.serving.metrics import (EXPOSITION, Reservoir, label_series,
                                     merge_series)
from tnn_tpu.serving.tracing import FlightRecorder, Tracer, span_name

KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=48)


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


def _spec_prompts():
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 128, 12).astype(np.int32)
    return [np.concatenate([prefix, rng.integers(0, 128, n).astype(
        np.int32)]) for n in (3, 5, 2, 4)]


def _spec_run(model, params, *, trace):
    """Spec-decode + prefix-cache workload: shared 12-token prefix so the
    second wave forks cached KV, ngram drafting so the mixed step runs the
    verify path — the two features whose step shapes tracing must not
    perturb."""
    eng = InferenceEngine(model, params, spec="ngram", spec_k=3,
                          trace=trace, **KW)
    prompts = _spec_prompts()
    rids = [eng.submit(p, 8) for p in prompts[:2]]
    eng.run_until_complete()                  # publishes the prefix
    rids += [eng.submit(p, 8) for p in prompts[2:]]
    out = eng.run_until_complete()
    assert eng.metrics.prefix_hits >= 1, "workload never hit the cache"
    return [out[r] for r in rids], eng


class TestSpanName:
    def test_attrs_appended_in_order(self):
        assert span_name("serve.step", trace="t3", rid=7, step=12) == \
            "serve.step trace=t3 rid=7 step=12"

    def test_none_attrs_dropped(self):
        assert span_name("serve.step", trace=None, rid=1) == "serve.step rid=1"

    def test_bare_base(self):
        assert span_name("serve.step") == "serve.step"


class TestTracer:
    def test_disabled_without_profiler(self):
        """No profiler session and no ``Profiler``: a span and an instant
        record nothing anywhere and raise nothing (the annotation sink is
        an atomic load outside a session)."""
        tr = Tracer()
        assert not tr.enabled
        with tr.span("serve.step", rid=1):
            pass
        tr.instant("serve.submit", rid=1, trace=None)
        assert tr.profiler is None

    def test_span_and_instant_record_events(self):
        prof = Profiler(source="engine")
        tr = Tracer(prof)
        assert tr.enabled
        with tr.span("serve.step", trace="t0", step=1):
            pass
        tr.instant("serve.submit", trace="t0", rid=4)
        names = [ev.name for ev in prof.events]
        assert "serve.step trace=t0 step=1" in names
        assert "serve.submit trace=t0 rid=4" in names
        inst = [ev for ev in prof.events if ev.name.startswith("serve.submit")]
        assert inst[0].duration == 0.0


def _spec_ref(model, params):
    """What the spec workload must emit, traced or not: the offline greedy
    reference (``models.gpt2.generate``) of each prompt."""
    from tnn_tpu.models.gpt2 import generate

    return [np.asarray(generate(model, params, p[None], 8,
                                max_len=KW["max_seq_len"]))[0].tolist()
            for p in _spec_prompts()]


@pytest.fixture(scope="module")
def flight_run(tiny_lm, tmp_path_factory):
    """One supervised run shared by the flight-recorder and terminal-event
    tests: crash at step 3 (crash dump + migration of both running rids),
    run to completion, then a graceful drain (drain dump)."""
    model, params = tiny_lm
    flight_dir = str(tmp_path_factory.mktemp("flight"))
    plan = FaultPlan(step_crash_calls=(3,))
    eng = InferenceEngine(model, params, faults=plan, **KW)
    events = []
    sup = EngineSupervisor(eng, event_sink=events.append,
                           restart_backoff_s=0.0, max_restarts=2,
                           flight_dir=flight_dir)
    rng = np.random.default_rng(4)
    rids = [sup.submit(rng.integers(0, 128, n).astype(np.int32), 5)
            for n in (5, 6)]
    sup.run_sync()
    sup.request_drain("test")
    sup.run_sync()
    return sup, rids, events


class TestTracedTokenExact:
    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_traced_equals_untraced(self, lm, family):
        model, params = lm
        got, eng = _spec_run(model, params, trace=True)
        assert got == _spec_ref(model, params), \
            f"tracing changed tokens of the {family} model"
        # and the trace is real: request-scoped events with trace ids
        names = [ev.name for ev in eng.profiler.events]
        assert any(n.startswith("serve.submit") for n in names)
        assert any(n.startswith("serve.finish") for n in names)
        assert any("trace=t0" in n for n in names)

    def test_traced_clean_under_debug_sync(self, tiny_lm, monkeypatch):
        """Tracing instants/spans are host-side bookkeeping: a traced step
        under jax.transfer_guard('disallow') neither syncs nor diverges."""
        model, params = tiny_lm
        ref = _spec_ref(model, params)
        monkeypatch.setenv("TNN_DEBUG_SYNC", "1")
        got, eng = _spec_run(model, params, trace=True)
        assert eng.debug_sync
        assert got == ref

    def test_terminal_event_carries_breakdown(self, flight_run):
        sup, rids, events = flight_run
        term = [e for e in events if e["event"] == "done"]
        assert len(term) == len(rids)
        for ev in term:
            assert ev["trace_id"] == f"t{ev['id']}"
            bd = ev["latency_breakdown"]
            assert set(bd) == {"queued_ms", "prefill_ms", "decode_ms",
                               "stalled_ms", "host_gap_ms", "preemptions",
                               "migrations"}
            assert bd["prefill_ms"] > 0 and bd["decode_ms"] > 0
        # both requests were RUNNING at the crash -> both crash-migrated,
        # and the breakdown says so
        assert all(ev["latency_breakdown"]["migrations"] >= 1 for ev in term)


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record({"step_seq": i})
        assert len(rec) == 4
        assert [r["step_seq"] for r in rec.records()] == [6, 7, 8, 9]

    def test_dump_schema(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.record({"step_seq": 1, "queued": 0})
        path = rec.dump(str(tmp_path / "f.jsonl"), "drain",
                        extra={"restarts": 0})
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        meta = lines[0]
        assert meta["kind"] == "flight_recorder_meta"
        assert meta["reason"] == "drain"
        assert meta["capacity"] == 8 and meta["records"] == 1
        assert meta["total_steps_seen"] == 1 and meta["restarts"] == 0
        assert lines[1]["step_seq"] == 1

    def test_crash_dump_last_record_is_crashing_step(self, flight_run):
        """Under faults.step_crash the supervisor writes a crash dump whose
        final record carries the crashing step's batch (the rids that were
        RUNNING), the crash marker, and the exception text."""
        sup, rids, _ = flight_run
        assert sup.restarts == 1
        crash_dumps = [p for p in sup.flight_dumps if "crash" in p]
        assert len(crash_dumps) == 1
        lines = [json.loads(ln) for ln in open(crash_dumps[0]) if ln.strip()]
        assert lines[0]["kind"] == "flight_recorder_meta"
        assert lines[0]["reason"] == "crash"
        last = lines[-1]
        assert last["crashed"] is True
        assert "EngineCrash" in last["error"]
        assert sorted(last["running_rids"]) == sorted(rids)
        assert last["step_seq"] == 3
        # the crashed step ends the dump — nothing recorded after it
        assert all("crashed" not in ln for ln in lines[1:-1])

    def test_drain_dump_and_step_record_shape(self, flight_run):
        sup, _, _ = flight_run
        assert sup.state is SupervisorState.STOPPED
        drain = [p for p in sup.flight_dumps if "drain" in p]
        assert len(drain) == 1
        lines = [json.loads(ln) for ln in open(drain[0]) if ln.strip()]
        assert len(lines) >= 2
        rec = lines[1]
        for key in ("step_seq", "queued", "running_rids", "programs",
                    "step_latency_s", "pool_allocated", "pool_evictable",
                    "faults_fired"):
            assert key in rec, f"step record lacks {key}"
        prog = rec["programs"][0]
        assert set(prog) == {"kind", "compile_key", "rids", "fill"}

    def test_no_dir_no_dump(self, tiny_lm):
        model, params = tiny_lm
        sup = EngineSupervisor(InferenceEngine(model, params, **KW))
        sup.flight.record({"step_seq": 1})
        assert sup._dump_flight("drain") is None    # flight_dir unset
        assert sup.flight_dumps == []


class TestReservoirCap:
    def test_algorithm_r_bounds_memory(self):
        r = Reservoir("ttft_s", cap=16)
        for i in range(10_000):
            r.append(float(i))
        assert len(r) == 16
        assert r.seen == 10_000
        assert all(0 <= x < 10_000 for x in r)

    def test_deterministic_for_fixed_name(self):
        a, b = Reservoir("x", cap=8), Reservoir("x", cap=8)
        for i in range(1000):
            a.append(float(i)), b.append(float(i))
        assert list(a) == list(b)

    def test_metrics_series_stay_bounded(self):
        """The regression this satellite exists for: per-request sample
        lists must not grow linearly with requests served."""
        m = ServingMetrics(reservoir_size=32)
        for i in range(5000):
            m.observe_ttft(0.001 * i)
            m.observe_decode(num_tokens=1, seconds=0.002, batch_width=1)
            m.observe_queue_wait(0.003)
            m.observe_step_latency(0.004)
        for series in (m.ttft_s, m.token_latency_s, m.queue_wait_s,
                       m.step_latency_s):
            assert len(series) <= 32
        s = m.summary()
        assert s["ttft_ms_p50"] > 0     # percentiles still answer
        # histograms keep EXACT counts even though the reservoir samples
        assert m.histograms["serve.ttft_s"].count == 5000


class TestPrometheusExposition:
    def _parse(self, text):
        """Minimal 0.0.4 parser: returns (helps, types, samples)."""
        helps, types, samples = {}, {}, []
        for ln in text.splitlines():
            if ln.startswith("# HELP "):
                _, _, name, h = ln.split(" ", 3)
                helps[name] = h
            elif ln.startswith("# TYPE "):
                _, _, name, t = ln.split(" ", 3)
                types[name] = t
            elif ln:
                metric, value = ln.rsplit(" ", 1)
                labels = {}
                if "{" in metric:
                    metric, _, rest = metric.partition("{")
                    for pair in rest.rstrip("}").split(","):
                        k, _, v = pair.partition("=")
                        labels[k] = v.strip('"')
                samples.append((metric, labels, float(value)))
        return helps, types, samples

    def test_exposition_parses(self):
        # direct ServingMetrics population: the engine-backed scrape path
        # is tier-1 in tests/test_server.py; this checks the text contract
        m = ServingMetrics()
        for i in range(3):
            m.observe_ttft(0.01 * (i + 1))
            m.observe_step_latency(0.002 * (i + 1))
            m.observe_decode(num_tokens=2, seconds=0.004, batch_width=2)
        m.observe_gauges(queue_depth=2, pool_occupancy=0.5)
        m.finished = 3
        text = render_prometheus(m.prometheus_series())
        helps, types, samples = self._parse(text)
        assert types["tnn_serve_ttft_seconds"] == "histogram"
        assert types["tnn_serve_steps_total"] == "counter"
        assert types["tnn_serve_queue_depth"] == "gauge"
        # every sample's family carries HELP and TYPE headers
        fams = {m.split("{")[0] for m, _, _ in samples}
        for fam in fams:
            base = fam
            for suffix in ("_bucket", "_sum", "_count"):
                if base.endswith(suffix):
                    base = base[: -len(suffix)]
                    break
            assert base in types and base in helps, f"bare series {fam}"
        # histogram contract: cumulative buckets, +Inf == count
        buckets = [(lb, v) for m, lb, v in samples
                   if m == "tnn_serve_step_latency_seconds_bucket"]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "buckets must be cumulative"
        assert buckets[-1][0]["le"] == "+Inf"
        count = [v for m, lb, v in samples
                 if m == "tnn_serve_step_latency_seconds_count"][0]
        assert buckets[-1][1] == count > 0

    def test_every_exposition_key_renders(self, tiny_lm):
        """The registry IS the exposition: every registered family appears
        in the rendered text even at zero."""
        text = render_prometheus(ServingMetrics().prometheus_series())
        for name, _, _, _ in EXPOSITION.values():
            assert f"# TYPE {name.removesuffix('_total')}" in text or \
                f"# TYPE {name}" in text, f"{name} missing from exposition"

    def test_label_and_merge_series(self):
        fams = ServingMetrics().prometheus_series()
        a = label_series(fams, {"replica": "0"})
        b = label_series(fams, {"replica": "1"})
        merged = merge_series(a, b)
        names = [f["name"] for f in merged]
        assert len(names) == len(set(names)), "merge must dedupe families"
        one = merged[0]
        replicas = {lbls.get("replica") for _, lbls, _ in one["samples"]}
        assert replicas == {"0", "1"}

    @pytest.mark.slow   # tier-1 twin: test_server's raw-socket router scrape
    def test_router_labels_survive_replica_kill(self, tiny_lm):
        """After a replica dies the exposition still renders, keeps the
        router's own series, and keeps the survivor's labeled series."""
        model, params = tiny_lm
        sups = [EngineSupervisor(InferenceEngine(model, params, **KW))
                for _ in range(2)]
        router = Router(sups, seed=0, profiler=Profiler(source="router"))
        term = []
        for i in range(4):
            router.submit(np.arange(1, 6, dtype=np.int32) + i, 4,
                          listener=lambda ev: (
                              term.append(ev) if ev["event"] != "token"
                              else None))
        router.run_sync(max_rounds=500)
        assert len(term) == 4
        router.kill_replica(0)
        router.pump(5)
        text = render_prometheus(router.prometheus_series())
        helps, types, samples = self._parse(text)
        labels = {lb.get("replica") for _, lb, _ in samples}
        assert "router" in labels and "1" in labels
        # supervisor-level families present under the replica label
        assert any(m == "tnn_serve_supervisor_restarts" for m, _, _ in
                   samples)


# ------------------------------------------------- PR 24: the second sink ----

def _host_events(trace_dir, prefixes=("serve.", "front.", "train.")):
    """[(thread line, name, start ns, end ns, stats)] of the program's spans
    in the profile ``start_trace`` wrote under ``trace_dir``."""
    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((line.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _start_trace(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the program's spans, not its frames
    jax.profiler.start_trace(str(path), profiler_options=opts)


PHASES = ("serve.build", "serve.dispatch", "serve.fetch", "serve.commit")


class TestProfilerSink:
    def test_engine_phases_in_the_host_plane(self, tiny_lm, tmp_path):
        """A plain engine (no ``trace=True``, no ``Profiler``) under a
        profiler session: every step leaves its four phase spans, with the
        step as a stat and not as part of the name, on the one thread that
        drove it, disjoint or properly nested."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW)
        rng = np.random.default_rng(3)
        rids = [eng.submit(rng.integers(0, 128, 7).astype(np.int32), 4)
                for _ in range(2)]
        eng.step()                       # compile outside the session
        _start_trace(tmp_path)
        try:
            out = eng.run_until_complete()
        finally:
            jax.profiler.stop_trace()
        assert all(len(out[r]) == 4 for r in rids)
        assert eng.profiler is None and not eng.tracer.enabled
        evs = _host_events(tmp_path)
        by_name = {}
        for ev in evs:
            by_name.setdefault(ev[1], []).append(ev)
        for name in PHASES:
            assert by_name.get(name), f"no {name} event in the host plane"
            assert all("step" in ev[4] for ev in by_name[name]), name
        assert {"kind", "program"} <= set(by_name["serve.dispatch"][0][4])
        # a paged step program says what a grid step of its kernel fetches:
        # 12 table entries (all of a row's 48 positions) of the ONE page row
        # that holds both heads of 16 side by side (``kv_lane_pack`` 2)
        paged = [ev[4] for ev in by_name["serve.dispatch"]
                 if ev[4]["kind"] == "decode_paged"]
        assert paged and all(
            (int(a["attn_pages"]), int(a["attn_heads"]),
             int(a["kv_lane_pack"])) == (12, 1, 2) for a in paged)
        assert eng.stats()["kv_lane_pack"] == 2
        assert not any(" " in ev[1] or "=" in ev[1] for ev in evs)
        assert len({ev[0] for ev in evs if ev[1] in PHASES}) == 1
        # the phases tile the worker's time: no two of them overlap, and
        # anything else on the thread lies inside one of them or outside all
        spans = sorted(ev[2:4] for ev in evs if ev[1] in PHASES)
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
        for ev in evs:
            if ev[1] not in PHASES:
                assert all(ev[3] <= s or ev[2] >= e
                           or (s <= ev[2] and ev[3] <= e) for s, e in spans)
        # a step's phases share its step stat, in order
        first = min(ev[4]["step"] for ev in by_name["serve.fetch"])
        order = [ev[1] for ev in sorted(evs, key=lambda ev: ev[2])
                 if ev[1] in PHASES and ev[4]["step"] == first + 1]
        assert order == list(PHASES)
        # and the reservoirs were fed where the work happened
        s = eng.metrics.summary()
        assert s["build_ms_p50"] > 0 and s["commit_ms_p50"] > 0
        assert 0 < s["attn_fetch_fill_mean"] <= 3 / 12  # <= 11 positions
        assert "serve.host_gap" not in by_name      # the gap is between spans

    def test_training_spans_in_the_host_plane(self, tmp_path):
        from tnn_tpu import nn
        from tnn_tpu.data.token_stream import TokenStreamDataLoader
        from tnn_tpu.models.gpt2 import GPT2
        from tnn_tpu.train.step import create_train_state, make_train_step

        path = tmp_path / "train.bin"
        np.random.default_rng(0).integers(0, 64, 4096, dtype=np.uint16) \
            .tofile(path)
        loader = TokenStreamDataLoader(str(path), 16)
        model = GPT2(vocab_size=64, max_len=16, num_layers=1, d_model=16,
                     num_heads=2)
        opt = nn.AdamW(lr=1e-3, grad_clip_norm=1.0)
        state = create_train_state(model, opt, jax.random.PRNGKey(0), (2, 16))
        step = make_train_step(model, opt, compute_accuracy=False)

        def one(state):
            data, labels = loader.random_windows(2)
            return step(state, jax.numpy.asarray(data, jax.numpy.int32),
                        jax.numpy.asarray(labels, jax.numpy.int32))[0]

        state = one(state)
        _start_trace(tmp_path / "trace")
        try:
            for _ in range(3):
                state = one(state)
            jax.block_until_ready(state)
        finally:
            jax.profiler.stop_trace()
        evs = _host_events(tmp_path / "trace")
        inputs = [ev for ev in evs if ev[1] == "train.input"]
        assert len(inputs) == 3 and inputs[0][4] == {"rows": 2}
        assert len([ev for ev in evs if ev[1] == "train.dispatch"]) == 3

    def test_front_end_records_emit_delay_into_the_current_registry(
            self, tiny_lm, monkeypatch, capsys):
        """``_serve_stdin`` stamps token events as the worker hands them
        over and records the wait at flush, into whatever registry the
        engine holds THEN (the benchmark swaps it to mark a window)."""
        import argparse

        import tnn_tpu.cli.serve as serve_cli

        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW)
        sup = EngineSupervisor(eng)
        rfd, wfd = os.pipe()
        seen = {}

        def feed():
            with os.fdopen(wfd, "w") as w:
                w.write(json.dumps({"id": "a", "tokens": [1, 2, 3],
                                    "max_new_tokens": 3}) + "\n")
                w.flush()
                seen["before"] = eng.metrics
                eng.metrics = seen["swapped"] = ServingMetrics()
                w.write(json.dumps({"id": "b", "tokens": [4, 5, 6],
                                    "max_new_tokens": 3}) + "\n")

        with os.fdopen(rfd, "r") as rd:
            monkeypatch.setattr("sys.stdin", rd)
            t = threading.Thread(target=feed)
            t.start()
            rc = serve_cli._serve_stdin(
                sup, model, None,
                argparse.Namespace(max_new_tokens=3, deadline_s=0.0))
            t.join()
        assert rc == 0
        events = [json.loads(ln) for ln in
                  capsys.readouterr().out.splitlines() if ln.startswith("{")]
        assert sum(ev["event"] == "token" for ev in events) == 6
        assert all(set(ev) == {"event", "id", "token"}
                   for ev in events if ev["event"] == "token")
        n = len(seen["before"].emit_delay_s) + len(seen["swapped"].emit_delay_s)
        assert n == 6 and len(seen["swapped"].emit_delay_s) >= 1
        assert seen["swapped"].summary()["emit_delay_ms_p50"] >= 0.0


# ------------------------------------ PR 39: the step boundary's account ----

NEW_KEYS = ("put_ms_p50", "launch_ms_p50", "step_latency_ms_mean",
            "step_latency_ms_max", "speculate_refused_mixed_step_share",
            "front_late_ms_total", "front_late_ms_max")
REFUSALS = ("mixed_step", "row_ends", "admission", "pool", "row_condition",
            "other")


def _overlap_run(model, params, *, trace):
    """A plain workload through the overlapped loop (every family takes
    it): prompts of two chunks, outputs that end at different steps, so
    mixed steps, steps built, steps dispatched ahead and refusals all
    occur."""
    eng = InferenceEngine(model, params, overlap=True, prefix_cache=False,
                          chunk_size=8, trace=trace, **KW)
    rng = np.random.default_rng(5)
    rids = [eng.submit(rng.integers(0, 128, n).astype(np.int32), new)
            for n, new in ((11, 6), (9, 10), (13, 8))]
    out = eng.run_until_complete()
    return [out[r] for r in rids], eng


def _inside(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


class TestStepBoundary:
    @pytest.mark.parametrize("family", ["gpt2", "llama", "mistral"])
    def test_traced_equals_untraced_with_overlap_on(self, lm, family):
        model, params = lm
        on, eng = _overlap_run(model, params, trace=True)
        off, plain = _overlap_run(model, params, trace=False)
        assert on == off, f"tracing changed tokens of the {family} model"
        names = [ev.name for ev in eng.profiler.events]
        for base in ("serve.put", "serve.launch", "serve.speculate"):
            assert any(n.startswith(base + " ") for n in names), base
        dispatches = [n for n in names if n.startswith("serve.dispatch ")]
        assert any(" ahead=1 " in n for n in dispatches)
        # a dispatch names its program as the device profile will
        assert any(" program=tnn_serve_decode " in n for n in dispatches)
        assert any(" program=tnn_serve_mixed_w8 " in n for n in dispatches)
        assert all(" program=tnn_serve_" in n for n in dispatches)
        assert plain.metrics.summary()["adopted_step_share"] > 0

    def test_sub_spans_nest_and_tile_the_overlapped_worker(
            self, tiny_lm, tmp_path):
        """Under a profiler session the overlapped engine leaves, on one
        thread: ``serve.put`` / ``serve.launch`` inside ``serve.dispatch``
        (the token matrix of a mixed step that is BUILT: a ``serve.put``
        inside ``serve.build``), ``serve.speculate`` round the dispatch of a
        step that goes out ahead, with the ``kind`` of the step it goes out
        behind, every one with ``step``; no two spans overlap
        but one lies inside the other, so the innermost level is
        disjoint."""
        model, params = tiny_lm
        _overlap_run(model, params, trace=False)     # compile outside
        _start_trace(tmp_path)
        try:
            _, eng = _overlap_run(model, params, trace=False)
        finally:
            jax.profiler.stop_trace()
        evs = [ev for ev in _host_events(tmp_path) if ev[3] > ev[2]]
        by = {}
        for ev in evs:
            by.setdefault(ev[1], []).append(ev)
        for name in PHASES + ("serve.put", "serve.launch",
                              "serve.speculate"):
            assert by.get(name), f"no {name} event in the host plane"
            assert all("step" in ev[4] for ev in by[name]), name
        assert len({ev[0] for ev in evs if ev[1].startswith("serve.")}) == 1

        def parents(ev, names):
            return [o for n in names for o in by[n] if _inside(ev, o)
                    and o[4]["step"] == ev[4]["step"]]

        assert all(len(parents(ev, ["serve.dispatch"])) == 1
                   for ev in by["serve.launch"])
        assert len(by["serve.launch"]) == len(by["serve.dispatch"])
        in_build = [ev for ev in by["serve.put"]
                    if parents(ev, ["serve.build"])]
        assert all(len(parents(ev, ["serve.dispatch", "serve.build"])) == 1
                   for ev in by["serve.put"])
        mixed = [ev for ev in by["serve.dispatch"]
                 if ev[4]["kind"] == "mixed"]
        built = [ev for ev in mixed if not int(ev[4]["ahead"])]
        assert len(in_build) == len(built) > 0
        assert len(mixed) > len(built)      # chunks go out ahead as well
        # serve.dispatch says how far ahead of the committed state it went,
        # and which program it launched
        assert all("ahead" in ev[4] for ev in by["serve.dispatch"])
        assert {ev[4]["program"] for ev in by["serve.dispatch"]} \
            == {"tnn_serve_decode", "tnn_serve_mixed_w8"}
        ahead = [ev for ev in by["serve.dispatch"] if int(ev[4]["ahead"])]
        assert ahead and all(len(parents(ev, ["serve.speculate"])) == 1
                             for ev in ahead)
        # a serve.speculate either holds the dispatch of the step it names
        # or was refused, and the registry counted why
        went = [ev for ev in by["serve.speculate"]
                if any(_inside(d, ev) for d in ahead)]
        assert len(went) == len(ahead)
        s = eng.metrics.summary()
        assert len(by["serve.speculate"]) - len(went) == sum(
            s[f"speculate_refused_{r}"] for r in REFUSALS)
        assert s["speculate_refused_mixed_step"] == 0
        assert {ev[4]["kind"] for ev in by["serve.speculate"]} \
            == {"decode", "mixed"}
        # laminar: any two spans of the worker are disjoint or nested
        spans = sorted((ev for ev in evs if ev[1].startswith("serve.")),
                       key=lambda ev: (ev[2], -ev[3]))
        for i, a in enumerate(spans):
            for b in spans[i + 1:]:
                if b[2] >= a[3]:
                    break
                assert b[3] <= a[3], (a[1], b[1])
        # between two fetches the worker is in a span most of the time
        # again: what is left unnamed is the loop's own few lines
        fetches = sorted(by["serve.fetch"], key=lambda ev: ev[2])
        top = [ev for ev in spans if not any(
            _inside(ev, o) and o is not ev for o in spans)]
        covered = sum(ev[3] - ev[2] for ev in top
                      if fetches[0][2] <= ev[2] and ev[3] <= fetches[-1][3])
        assert covered > 0.8 * (fetches[-1][3] - fetches[0][2])

    def test_new_summary_keys_and_their_exposition(self, tiny_lm):
        fresh = ServingMetrics()
        s = fresh.summary()
        assert all(s[k] == 0.0 for k in NEW_KEYS)
        for key, summary_key in (("serve.put_s", "put_ms_p50"),
                                 ("serve.launch_s", "launch_ms_p50"),
                                 ("serve.front_late_s",
                                  "front_late_ms_total")):
            assert EXPOSITION[key][3] == summary_key
        text = render_prometheus(fresh.prometheus_series())
        for fam in ("tnn_serve_put_seconds_total",
                    "tnn_serve_launch_seconds_total",
                    "tnn_serve_front_late_seconds_total",
                    "tnn_serve_step_latency_max_seconds",
                    "tnn_serve_front_late_max_seconds"):
            assert f"# TYPE {fam}" in text, fam
        # ONE decode step into a fresh registry: its put and its launch are
        # two halves of its dispatch, whose length the Profiler sink holds
        model, params = tiny_lm
        eng = InferenceEngine(model, params, trace=True, **KW)
        eng.submit(np.arange(5, dtype=np.int32), 6)
        eng.step()
        eng.step()
        eng.metrics = ServingMetrics()
        before = len(eng.profiler.events)
        eng.step()
        s = eng.metrics.summary()
        dispatch = [ev for ev in eng.profiler.events[before:]
                    if ev.name.startswith("serve.dispatch")]
        assert len(dispatch) == 1 and "kind=decode_paged" in dispatch[0].name
        assert 0 < s["put_ms_p50"] and 0 < s["launch_ms_p50"]
        assert s["put_ms_p50"] + s["launch_ms_p50"] \
            <= 1e3 * dispatch[0].duration
        assert s["step_latency_ms_mean"] == pytest.approx(
            s["step_latency_ms_max"]) and s["step_latency_ms_max"] > 0
        # the mean is exact over every step, the share is over steps committed
        m = ServingMetrics(reservoir_size=4)
        for i in range(100):
            m.observe_step_latency(0.001 * (1 + i % 10))
        for _ in range(30):
            m.observe_speculate_refusal("mixed_step")
        s = m.summary()
        assert s["step_latency_ms_mean"] == pytest.approx(5.5)
        assert s["step_latency_ms_max"] == pytest.approx(10.0)
        assert s["speculate_refused_mixed_step_share"] == pytest.approx(0.3)

    def test_front_end_counts_how_late_its_empty_polls_came_back(
            self, tiny_lm, monkeypatch, capsys):
        """An empty poll of stdin that comes back late counts by how much;
        one that returned lines counts nothing, however late."""
        import argparse

        import tnn_tpu.cli.serve as serve_cli

        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW)
        sup = EngineSupervisor(eng)
        rfd, wfd = os.pipe()
        real, seen = serve_cli._read_stdin_lines, {"empty": 0, "late": []}

        def slow(fd, pending, timeout):
            out = real(fd, pending, timeout)
            if out[0] or out[2]:
                time.sleep(0.04)        # lines (or EOF), late: not counted
            else:
                seen["empty"] += 1
                if seen["empty"] == 1:
                    time.sleep(0.03)    # an empty poll, 30 ms late
            return out

        observe = ServingMetrics.observe_front_late
        monkeypatch.setattr(serve_cli, "_read_stdin_lines", slow)
        monkeypatch.setattr(
            ServingMetrics, "observe_front_late",
            lambda self, s: (seen["late"].append(s), observe(self, s))[1])

        def feed():
            with os.fdopen(wfd, "w") as w:
                w.write(json.dumps({"id": "a", "tokens": [1, 2, 3],
                                    "max_new_tokens": 3}) + "\n")
                w.flush()
                time.sleep(0.25)        # the front end polls, and finds nothing

        with os.fdopen(rfd, "r") as rd:
            monkeypatch.setattr("sys.stdin", rd)
            t = threading.Thread(target=feed)
            t.start()
            rc = serve_cli._serve_stdin(
                sup, model, None,
                argparse.Namespace(max_new_tokens=3, deadline_s=0.0))
            t.join(10.0)
        assert rc == 0 and not t.is_alive()
        capsys.readouterr()
        assert seen["empty"] >= 1 and len(seen["late"]) == seen["empty"]
        assert seen["late"][0] >= 0.03 and min(seen["late"]) >= 0.0
        s = eng.metrics.summary()
        assert s["front_late_ms_max"] == pytest.approx(1e3 * max(seen["late"]))
        assert s["front_late_ms_total"] == pytest.approx(
            1e3 * sum(seen["late"]))

    def test_what_the_added_spans_cost_a_step_with_no_session(
            self, tiny_lm, monkeypatch):
        """With nobody recording, what the engine's own ``_dispatch`` and
        ``try_speculate`` take around a program and a build that do nothing:
        the spans (``serve.dispatch`` with ``serve.put`` / ``serve.launch``,
        ``serve.speculate``), the clock reads, the per-key attributes'
        lookup, the samples. That is all a step pays for the account, with
        what ``serve.dispatch`` cost before it; a mixed step adds the one
        annotation round its token matrix. The bound is one no loaded test
        machine can miss; the number itself is printed (``pytest -s``) and
        reported in CHANGES.md (under 20 us on an idle machine)."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, overlap=True, **KW)
        eng.submit(np.arange(5, dtype=np.int32), 12)
        eng.step()
        eng.step()
        eng.begin_step()                # a decode step in flight
        temps = np.zeros(KW["max_batch_size"], np.float32)
        refusals = iter(["", "row_ends"] * 200)

        def program(*args):
            return None

        def a_step_ahead_that_builds_nothing(flight):
            eng._dispatch(program, "decode_paged", temps, 1, tuple, ahead=1)
            return next(refusals)

        monkeypatch.setattr(eng, "_dispatch_ahead",
                            a_step_ahead_that_builds_nothing)
        samples = []
        for _ in range(200):
            t0 = time.perf_counter()
            eng.try_speculate()
            samples.append(time.perf_counter() - t0)
        median_us = 1e6 * statistics.median(samples)
        print(f"_dispatch inside try_speculate, no session, nothing to put "
              f"or launch: {median_us:.2f} us a step")
        assert median_us < 100
        assert eng.metrics.summary()["speculate_refused_row_ends"] == 100


# ------------------------------- PR 24: names that survive a refactor ----

BLOCK_SCOPES = ("attn_qkv", "kv_write", "paged_attn", "attn_out", "mlp")
MODEL_SCOPES = ("embed", "h0", "h1", "ln_f", "lm_head")


def _lowered(jitted, *args):
    """A jitted program's lowered text with its ops' scope paths."""
    return jitted.lower(*args).as_text(debug_info=True)


class TestStableNames:
    @pytest.fixture()
    def as_on_the_chip(self, monkeypatch):
        """Take the kernels' path (``backend="auto"`` asks the default
        backend) in interpret mode: the names are the chip's."""
        from tnn_tpu.ops.pallas import paged_attention as pa

        monkeypatch.setenv("TNN_PALLAS_INTERPRET", "1")
        # the tiny model's page rows do not fill the 128 lanes the row
        # write's kernel asks for on the chip: the names are held here
        monkeypatch.setattr(pa, "_kernel_writes", lambda pages: True)
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            yield

    def test_serving_steps_carry_the_catalog(self, tiny_lm, as_on_the_chip):
        jnp = jax.numpy
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **KW)
        b, nb = KW["max_batch_size"], eng.blocks_per_seq
        row = dict(t=jnp.zeros(b), k=jnp.zeros(b, jnp.int32), p=jnp.zeros(b))
        tables = jnp.zeros((b, nb), jnp.int32)
        ints = jnp.zeros(b, jnp.int32)
        decode = _lowered(
            eng._step_program(None), params, eng.pool.cache, ints, ints, None,
            tables, row["t"], row["k"], row["p"], eng._key, row["t"])
        mixed = _lowered(
            eng._step_program(8), params, eng.pool.cache,
            jnp.zeros((b, 8), jnp.int32), ints, ints + 1, tables,
            row["t"], row["k"], row["p"], eng._key, row["t"])
        for text, module in ((decode, "jit_tnn_serve_decode"),
                             (mixed, "jit_tnn_serve_mixed_w8")):
            assert f"module @{module} " in text
            for scope in MODEL_SCOPES + BLOCK_SCOPES + ("sample",):
                assert re.search(rf"[/(]{scope}[/)]", text), (module, scope)
            assert "/paged_attn/tnn_paged_attention/" in text
            # ONE jitted function, called under each layer's scope
            assert "/h1/kv_write/jit(_write_rows_pallas)" in text
            assert '"tnn_kv_row_write/' in text
            assert not re.search(r"/kv_write/(scatter|gather)", text)

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_a_mixed_step_holds_no_cube_of_logits(self, family):
        """Every model's mixed program takes the head at each row's last
        live position (PR 48; until then only a model with state slots did,
        and a plain model's program ran the head over all ``B x qw``
        positions and cut one out of a float32 ``(B, qw, V)`` cube after
        the product): the engine's own builder, lowered with abstract
        arguments, holds the logits of ONE position a row and no cube. The
        vocabulary of 131 is no other width of these models."""
        from tnn_tpu.models.gpt2 import GPT2
        from tnn_tpu.models.llama import Llama

        jnp = jax.numpy
        size = dict(vocab_size=131, max_len=64, num_layers=2, d_model=32,
                    num_heads=2)
        model = GPT2(**size) if family == "gpt2" else Llama(**size)
        params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
        eng = InferenceEngine(model, params, **KW)
        b, nb, qw = KW["max_batch_size"], eng.blocks_per_seq, 8

        def spec_of(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt)

        ints, reals = spec_of((b,), jnp.int32), spec_of((b,), jnp.float32)
        text = eng._step_program(qw).lower(
            params, eng.pool.cache, spec_of((b, qw), jnp.int32), ints, ints,
            spec_of((b, nb), jnp.int32), reals, ints, reals,
            spec_of((2,), jnp.uint32), reals).as_text()
        assert f"tensor<{b}x1x131xf32>" in text
        assert not re.search(rf"tensor<{b}x{qw}x131x", text)
        assert not re.search(rf"tensor<{b * qw}x131x", text)

    def test_train_step_carries_the_catalog(self, as_on_the_chip):
        from tnn_tpu import nn
        from tnn_tpu.models.gpt2 import GPT2
        from tnn_tpu.train.step import create_train_state, make_train_step

        jnp = jax.numpy
        model = GPT2(vocab_size=64, max_len=128, num_layers=2, d_model=32,
                     num_heads=2, backend="pallas")
        opt = nn.AdamW(lr=1e-3, grad_clip_norm=1.0)
        state = create_train_state(model, opt, jax.random.PRNGKey(0),
                                   (2, 128))
        made = []
        real_jit = jax.jit
        with mock.patch.object(
                jax, "jit", lambda f, **kw: made.append(
                    real_jit(f, **kw)) or made[-1]):
            make_train_step(model, opt, compute_accuracy=True)
        ids = jnp.zeros((2, 128), jnp.int32)
        text = _lowered(made[-1], state, ids, ids, jnp.ones(()))
        assert "module @jit_tnn_train_step " in text
        for scope in MODEL_SCOPES + ("attn_qkv", "flash_attn", "attn_out",
                                     "mlp", "loss", "metrics", "optimizer",
                                     "grad_clip"):
            assert re.search(rf"[/(]{scope}[/)]", text), scope
        # one word finds forward and backward
        assert re.search(r"/jvp\(h1\)/mlp/", text)
        assert re.search(r"/transpose\(jvp\(h1\)\)/mlp/", text)
        assert "/flash_attn/tnn_flash_fwd/" in text
        assert re.search(r"/flash_attn/tnn_flash_bwd_\w+/", text)
        assert "/optimizer/grad_clip/" in text
