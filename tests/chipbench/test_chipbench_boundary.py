"""The step-boundary reader (PR 39), on the CPU: the join of programs to
their ``serve.dispatch`` by the runtime's ``run_id``, the clock's interval and
the account of the idle time by hand on built scenes, then on a trace the program's own engine left
on a v5e (``reduce/record_boundary.py``), and the fifteen entries. No chip,
no network, no topology."""
import json
import os
import shutil
import types

import pytest

from chipbench import spec
from chipbench.readers import (summary_key, trace_module_p50,
                               trace_module_share, trace_step_boundary)
from chipbench.reduce import record_boundary, xplane_meta

RECORDED = os.path.join(spec.ROOT, "chipbench", "reduce",
                        "sample_v5e_boundary.xplane.pb")
SERVING = ["gpt2-large.decode", "evabyte-pp2.decode-docs",
           "mistral-small4-ep4.decode-long", "trinity-large-ep8.decode-mixed"]
COUNTERS = ("adopted_step_share.tok", "refused_mixed_step_share.tok",
            "step_mean_ms.tok", "host_put_p50_ms.tok",
            "host_launch_p50_ms.tok", "front_late_total_ms.tok",
            "front_late_max_ms.tok")
TRACED = ("mixed_step_device_p50_ms.tok", "mixed_busy_share.tok",
          "idle_fetch_return_share.tok", "idle_host_share.tok",
          "idle_dispatch_share.tok", "idle_launch_to_start_share.tok",
          "idle_before_ahead_share.tok", "clock_offset_ms.tok")
NEW = COUNTERS + TRACED
SHARES = {"idle_fetch_return_share.tok": "fetch_return",
          "idle_host_share.tok": "host", "idle_dispatch_share.tok": "dispatch",
          "idle_launch_to_start_share.tok": "launch_to_start"}

# ---------------------------------------------------------- a built scene ----
# Milliseconds on the DEVICE's clock; the host's clock reads OFFSET less. Four
# steps: a mixed step built, a decode step built behind it, a decode step
# dispatched ahead, a decode step built after a row's end. A program is
# (name, start, end, enqueued, called back), the last two the runtime's own
# events on the host. Program 1 starts the instant it is enqueued and its
# completion is called back the instant it ends, so the clock's interval is
# the one point OFFSET. Program 4's event opens 0.6 before its first op runs
# (the device has taken it up; its inputs are on their way): the gap in front
# of it ends where that op starts.
OFFSET = 3.0
PROGRAMS = [("jit_tnn_serve_mixed_w16(7)", 3.0, 13.0, 3.0, 13.0),
            ("jit_tnn_serve_decode(9)", 17.5, 25.5, 17.2, 25.6),
            ("jit_tnn_serve_decode(9)", 25.6, 33.6, 19.1, 33.7),
            ("jit_tnn_serve_decode(9)", 36.0, 44.6, 35.95, 44.8)]  # op at 36.6
MIXED = {"kind": "mixed", "program": "tnn_serve_mixed_w16"}
DECODE = {"kind": "decode_paged", "program": "tnn_serve_decode"}
SPANS = [   # (name, start, end, stats)
    ("serve.build", 0.0, 2.0, {"step": 1}),
    ("serve.put", 1.0, 1.4, {"step": 1}),
    ("serve.dispatch", 2.0, 4.0, {"step": 1, "ahead": 0, **MIXED}),
    ("serve.put", 2.0, 3.0, {"step": 1}),
    ("serve.launch", 3.0, 4.0, {"step": 1}),
    ("serve.speculate", 4.1, 4.2, {"step": 2}),
    ("serve.fetch", 4.5, 13.0, {"step": 1}),
    ("serve.commit", 13.0, 13.5, {"step": 1}),
    ("serve.build", 13.5, 15.0, {"step": 2}),
    ("serve.dispatch", 15.0, 17.0, {"step": 2, "ahead": 0, **DECODE}),
    ("serve.put", 15.0, 16.0, {"step": 2}),
    ("serve.launch", 16.0, 17.0, {"step": 2}),
    ("serve.speculate", 17.2, 19.2, {"step": 3}),
    ("serve.dispatch", 17.5, 19.0, {"step": 3, "ahead": 1, **DECODE}),
    ("serve.put", 17.5, 18.2, {"step": 3}),
    ("serve.launch", 18.2, 19.0, {"step": 3}),
    ("serve.fetch", 19.5, 25.7, {"step": 2}),
    ("serve.commit", 25.7, 26.0, {"step": 2}),
    ("serve.speculate", 26.0, 26.1, {"step": 4}),
    ("serve.fetch", 26.2, 33.8, {"step": 3}),
    ("serve.commit", 33.8, 34.2, {"step": 3}),
    ("serve.build", 34.2, 35.2, {"step": 4}),
    ("serve.dispatch", 35.2, 36.4, {"step": 4, "ahead": 0, **DECODE}),
    ("serve.put", 35.2, 35.9, {"step": 4}),
    ("serve.launch", 35.9, 36.4, {"step": 4}),
    ("serve.fetch", 36.8, 44.9, {"step": 4}),
]
# idle by hand: 0.2 inside program 1; 4.5 before program 2 = commit 0.5 +
# build 1.5 (host 2.0), dispatch 2.0, launch to start 0.5; 0.1 before the
# program dispatched ahead, all of it after its launch; 3.0 before program 4
# = the fetch's return 0.2, commit 0.4 + build 1.0, dispatch 1.2, 0.2
BY_HAND = {"fetch_return": 0.2, "host": 3.4, "dispatch": 3.2,
           "launch_to_start": 0.8, "other": 0.2}


def scene(programs=PROGRAMS, spans=SPANS, offset=OFFSET):
    """``obs`` as a traced run leaves it once the file is read: what
    ``xplane_meta.of`` keeps of it and what ``runs_of`` does."""
    ms = 1e-3

    def host(t):
        return None if t is None else (t - offset) * ms

    ops, runs = [], []
    for name, start, end, enqueued, called_back in programs:
        runs.append({"name": name, "start": start * ms, "chip": "0",
                     "dur": (end - start) * ms, "enqueued": host(enqueued),
                     "called_back": host(called_back)})
        cut = 5.0 if (start, end) == (3.0, 13.0) else 0.0
        first = 36.6 if start == 36.0 else start
        for a, b in ([(start, start + cut), (start + cut + 0.2, end)]
                     if cut else [(first, end)]):
            ops.append({"name": "%fusion", "start": a * ms,
                        "dur": (b - a) * ms, "chip": "0", "tf_op": "",
                        "category": ""})
    modules = [{k: r[k] for k in ("name", "start", "dur", "chip")}
               for r in runs]
    on_host = [{"name": name, "start": host(a), "dur": (b - a) * ms,
                "thread": "engine/1", "stats": dict(stats)}
               for name, a, b, stats in spans]
    on_host.append({"name": "front.read", "start": 0.0, "dur": 50 * ms,
                    "thread": "main/2", "stats": {}})
    notes = []
    return {"trace_meta": {"ops": ops, "modules": modules, "spans": on_host,
                           "chips": 1}, "trace_runs": runs,
            "ctx": types.SimpleNamespace(note=notes.append), "notes": notes}


def account(obs):
    return trace_step_boundary.account(obs["trace_meta"], obs["trace_runs"])


def _parts_ms(got):
    return {k: round(1e3 * v, 6) for k, v in got["parts"].items()}


def test_a_known_offset_is_recovered_and_the_parts_add_up():
    obs = scene()
    got, why = account(obs)
    assert why == "" and (got["joined"], got["programs"]) == (4, 4)
    lower, upper, shift = got["clock"]
    assert lower == pytest.approx(3e-3) and upper == pytest.approx(3e-3)
    assert shift == pytest.approx(3e-3)
    assert _parts_ms(got) == pytest.approx(BY_HAND)
    assert sum(got["parts"].values()) == pytest.approx(got["idle"])
    assert got["idle"] == pytest.approx(7.8e-3)
    assert got["ahead"] == pytest.approx(0.1e-3)
    rows = {k: {p: round(1e3 * v, 6) for p, v in row.items()
                if round(1e3 * v, 6)} for k, row in got["by_next"].items()}
    assert rows == {
        "inside a program": {"other": 0.2},
        "decode built": {"fetch_return": 0.2, "host": 3.4, "dispatch": 3.2,
                         "launch_to_start": 0.7},
        "decode ahead": {"launch_to_start": 0.1}}
    # the reader: shares of the idle time, the absolute shift in ms, one note
    read = trace_step_boundary.read
    for part, ms in BY_HAND.items():
        assert read(obs, part=part) == pytest.approx(100 * ms / 7.8)
    assert read(obs, before="ahead") == pytest.approx(100 * 0.1 / 7.8)
    assert read(obs, part="clock") == pytest.approx(3.0)
    assert len(obs["notes"]) == 1 and "shift 3.000 ms" in obs["notes"][0]
    assert "before decode ahead: launch_to_start 0.100" in obs["notes"][0]


def test_clocks_that_agree_are_left_alone_and_a_negative_offset_too():
    got, _ = account(scene(offset=0.0))
    assert got["clock"] == pytest.approx((0.0, 0.0, 0.0))
    assert _parts_ms(got) == pytest.approx(BY_HAND)
    got, _ = account(scene(offset=-2.5))
    assert got["clock"][2] == pytest.approx(-2.5e-3)
    assert _parts_ms(got) == pytest.approx(BY_HAND)
    # an interval that holds 0 asks for no shift: program 1 starts 0.1 ms
    # after it was enqueued (its launch began 0.5 earlier; program 4's event
    # opens 0.05 after), and every completion is called back 0.2 ms or more
    # after its program ended
    moved = {("serve.put", 2.0): (2.0, 2.5), ("serve.launch", 3.0): (2.5, 4.0)}
    spans = [(n, *moved.get((n, a), (a, b)), st) for n, a, b, st in SPANS]
    loose = [(n, a, b, enq - 0.1 if i == 0 else enq, back + 0.2)
             for i, (n, a, b, enq, back) in enumerate(PROGRAMS)]
    got, _ = account(scene(loose, spans, offset=0.0))
    assert got["clock"] == pytest.approx((-0.2e-3, 0.05e-3, 0.0))
    # ... and one that does not is shifted by its end nearest 0
    got, _ = account(scene(loose, spans, offset=-0.15))
    assert got["clock"] == pytest.approx((-0.35e-3, -0.1e-3, -0.1e-3))
    # the programs of a key split bound the clock too, and nothing else
    split = [("jit__threefry_split(3)", 14.0, 14.01, 13.98, 14.02)]
    got, _ = account(scene(sorted(loose + split, key=lambda p: p[1]), spans,
                           offset=0.0))
    assert got["clock"] == pytest.approx((-0.01e-3, 0.02e-3, 0.0))
    assert (got["joined"], got["programs"]) == (4, 4)


def test_programs_cut_by_the_slices_edges_are_dropped():
    """A program enqueued before the thread's first recorded launch has no
    span to be joined to; one that begins after the host's recording ended
    has no enqueue in it; a dispatch whose program is that one is left
    over."""
    programs = ([("jit_tnn_serve_decode(9)", -9.0, -1.0, -9.5, None)]
                + PROGRAMS
                + [("jit_tnn_serve_decode(9)", 50.0, 58.0, None, None)])
    spans = SPANS + [
        ("serve.dispatch", 45.0, 45.6, {"step": 5, "ahead": 0, **DECODE}),
        ("serve.put", 45.0, 45.3, {"step": 5}),
        ("serve.launch", 45.3, 45.6, {"step": 5})]
    got, why = account(scene(programs, spans))
    assert why == ""
    assert (got["cut"], got["joined"], got["programs"]) == (2, 4, 6)
    assert got["clock"][2] == pytest.approx(3e-3)
    # program 0's end to program 1 is idle now, and counts from the thread's
    # first recorded span on: the build 1.6, the token matrix's serve.put
    # inside it 0.4 + the dispatch's own 1.0
    # ... and the 1.0 from program 4's end to the thread's last span lies in
    # front of a program that nothing can be joined to
    want = dict(BY_HAND, host=3.4 + 1.6, dispatch=3.2 + 1.4, other=0.2 + 1.0)
    assert _parts_ms(got) == pytest.approx(want)
    assert got["idle"] == pytest.approx(7.8e-3 + 3e-3 + 1e-3)
    assert set(got["by_next"]) == {"inside a program", "unjoined", "mixed",
                                   "decode built", "decode ahead"}


def test_a_program_launched_after_the_threads_last_span_is_cut():
    """The runtime's events are recorded for longer than the thread's spans
    (on the chip by some 30 ms: one traced run in six, and the driver's
    run that refused this PR once): a program enqueued after the thread's
    last recorded span ended was launched by a dispatch that is not in the
    recording. It is cut, and it takes nothing from the last launch that
    is."""
    late = ("jit_tnn_serve_decode(9)", 50.0, 58.0, 49.9, None)
    got, why = account(scene(PROGRAMS + [late]))
    assert why == ""
    assert (got["cut"], got["joined"], got["programs"]) == (1, 4, 5)
    assert got["clock"][2] == pytest.approx(3e-3)
    # program 4's end to the end of the thread's last span, 0.3, is idle
    # now, in front of a program that nothing can be joined to
    assert _parts_ms(got) == pytest.approx(dict(BY_HAND, other=0.2 + 0.3))
    assert got["by_next"]["unjoined"]["other"] == pytest.approx(0.3e-3)
    # enqueued while the thread's last span was still open, it is held to
    # the 99%: two programs behind the one launch, neither is that launch's
    inside = ("jit_tnn_serve_decode(9)", 50.0, 58.0, 44.85, None)
    got, why = account(scene(PROGRAMS + [inside]))
    assert got is None and "3 of 5" in why


def test_clocks_that_step_against_each_other_inside_the_slice():
    """On the chip the two clocks step against each other by up to 0.2 ms
    inside a slice of 3 s, most of the interval's own width (0.27 ms): L
    may pass U by ``CLOCK_STEP``, the shift is their midpoint and the note
    shows both. Further apart, the clock is broken."""
    def stepped(by):    # program 4's event opens ``by`` BEFORE its enqueue
        return PROGRAMS[:3] + [PROGRAMS[3][:3] + (36.0 + by, 44.8)]

    obs = scene(stepped(0.2))
    got, why = account(obs)
    assert why == ""
    assert got["clock"] == pytest.approx((3.0e-3, 2.8e-3, 2.9e-3))
    assert sum(got["parts"].values()) == pytest.approx(got["idle"])
    assert got["idle"] == pytest.approx(7.8e-3)
    # the host's spans lie 0.1 ms earlier than they were: each boundary
    # between a device's event and a host's moves by that and no more
    for part, ms in _parts_ms(got).items():
        assert abs(ms - BY_HAND[part]) <= 0.3 + 1e-9, part
    assert trace_step_boundary.read(obs, part="clock") == pytest.approx(2.9)
    assert "clock L 3.000 U 2.800 shift 2.900 ms" in obs["notes"][0]
    assert trace_step_boundary.CLOCK_STEP == pytest.approx(0.5e-3)
    got, why = account(scene(stepped(0.6)))
    assert got is None and "L 3.000 ms > U 2.400 ms" in why


def test_a_broken_clock_or_join_reads_none_and_says_why():
    # program 2's completion is "called back" 5 ms before it ended: L 8 > U 3
    early = [p[:4] + (p[4] - 5.1,) if i == 1 else p
             for i, p in enumerate(PROGRAMS)]
    obs = scene(early)
    got, why = account(obs)
    assert got is None and "L 8.000 ms > U 3.000 ms" in why
    assert trace_step_boundary.read(obs, part="host") is None
    assert trace_step_boundary.read(obs, part="clock") is None
    assert obs["notes"] == [f"step boundary: not read: {why}"]
    # a program of another kind than its dispatch says: under 99% joined
    other = [("jit_tnn_serve_mixed_w16(7)",) + p[1:] if i == 1 else p
             for i, p in enumerate(PROGRAMS)]
    got, why = account(scene(other))
    assert got is None and "3 of 4" in why and "99%" in why
    # two programs enqueued behind one launch: neither is that launch's
    twice = [p[:3] + (17.3,) + p[4:] if i == 2 else p
             for i, p in enumerate(PROGRAMS)]
    got, why = account(scene(twice))
    assert got is None and "2 of 4" in why
    # a recording without the runtime's events (a CPU's): no clock, no join
    bare = scene([p[:3] + (None, None) for p in PROGRAMS])
    assert trace_step_boundary.read(bare, part="host") is None
    assert "DoEnqueueProgram / CompleteCallbacks" in bare["notes"][0]
    # the parent of the PR that added serve.launch: nothing to join on
    obs = scene(spans=[s for s in SPANS
                       if s[0] not in ("serve.launch", "serve.put")])
    assert trace_step_boundary.read(obs, part="dispatch") is None
    assert "no serve.launch" in obs["notes"][0]
    # no trace at all, no device plane
    blank = {"trace_meta": None, "ctx": obs["ctx"]}
    assert trace_step_boundary.read(blank, part="host") is None
    assert trace_module_share.read(blank, pattern="x") is None
    host_only = scene()
    host_only["trace_meta"].update(ops=[], modules=[])
    assert trace_step_boundary.read(host_only, before="ahead") is None
    assert trace_module_share.read(host_only, pattern="x") is None


def test_a_dispatch_names_its_program_and_the_kind_that_follows():
    """The join holds a program to the name its ``serve.dispatch`` says
    (``program``, the engine's own: no key is parsed); the table's rows are
    by the kind of program that follows."""
    unnamed = [(n, a, b, {k: v for k, v in st.items() if k != "program"})
               for n, a, b, st in SPANS]
    got, why = account(scene(spans=unnamed))
    assert got is None and "0 of 4" in why
    spec = [(n, a, b, dict(st, kind="spec", program="tnn_serve_spec_w16")
             if st.get("kind") == "mixed" else st) for n, a, b, st in SPANS]
    programs = [("jit_tnn_serve_spec_w16(5)",) + PROGRAMS[0][1:]] \
        + PROGRAMS[1:]
    got, why = account(scene(programs, spec))
    assert why == "" and got["joined"] == 4
    assert trace_step_boundary.follows({"kind": "mixed"}) == "mixed"
    assert trace_step_boundary.follows({"kind": "spec"}) == "spec"
    assert trace_step_boundary.follows(
        {"kind": "decode_paged", "ahead": 0}) == "decode built"
    assert trace_step_boundary.follows(
        {"kind": "decode_paged", "ahead": 2}) == "decode ahead"


def test_module_share_by_hand():
    obs = scene()       # mixed 10 ms of 10 + 8 + 8 + 8.6
    pattern = spec.load_json("chipbench", "layer_metrics",
                             "mixed_busy_share.tok.json")["args"]["pattern"]
    assert trace_module_share.read(obs, pattern=pattern) \
        == pytest.approx(100 * 10 / 34.6)
    assert trace_module_share.read(obs, pattern="^jit_tnn_train") == 0.0
    assert trace_module_p50.read(obs, pattern=pattern) == pytest.approx(10.0)


# ------------------------------------- the engine's own trace, from a v5e ----

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recording as a traced run leaves it: a file under the run's
    trace directory, read by ``xplane_meta.of`` and by the reader itself."""
    notes, trace_dir = [], tmp_path_factory.mktemp("trace")
    shutil.copy(RECORDED, trace_dir / "sample.xplane.pb")
    return {"ctx": types.SimpleNamespace(note=notes.append,
                                         trace_dir=str(trace_dir)),
            "notes": notes}


def test_the_recorded_trace_is_small_and_whole():
    assert os.path.getsize(RECORDED) < 64 * 1024
    meta = xplane_meta.read_file(RECORDED)
    assert meta["chips"] == 1 and meta["ops"]
    names = {s["name"] for s in meta["spans"]}
    assert {"serve.build", "serve.dispatch", "serve.put", "serve.launch",
            "serve.speculate", "serve.fetch", "serve.commit"} <= names
    kinds = {m["name"].split("(")[0] for m in meta["modules"]}
    assert "jit_tnn_serve_decode" in kinds
    assert any(k.startswith("jit_tnn_serve_mixed_w") for k in kinds)
    # every dispatch names its program as the device's line does
    assert {"jit_" + s["stats"]["program"] for s in meta["spans"]
            if s["name"] == "serve.dispatch"} <= kinds
    # what the script keeps of a recording is a recording: trimmed again,
    # it reads the same
    with open(RECORDED, "rb") as f:
        data = f.read()
    assert record_boundary.trim(data) == data
    # the runtime's events and the programs' run ids are in it: every
    # program of the slice, the tiny ones of a key split too, bounds the clock
    runs = trace_step_boundary.runs_of(data)
    assert [(r["name"], r["start"], r["dur"]) for r in runs] == sorted(
        ((m["name"], m["start"], m["dur"]) for m in meta["modules"]),
        key=lambda m: m[1])
    assert all(r["enqueued"] is not None for r in runs)
    lower, upper = trace_step_boundary.clock(runs)
    assert lower <= upper and upper - lower < 0.5e-3
    assert trace_step_boundary.runs_of(b"") == []
    assert trace_step_boundary.clock([]) is None


def test_the_account_of_the_recorded_trace(recorded):
    got = trace_step_boundary.of(recorded)
    meta = xplane_meta.of(recorded)
    serving = [m for m in meta["modules"]
               if trace_step_boundary.PROGRAM.search(m["name"])]
    assert got["programs"] == len(serving) >= 8
    assert got["joined"] == got["programs"] - got["cut"]
    lower, upper, shift = got["clock"]
    assert lower <= shift <= upper and abs(shift) < 20e-3
    assert got["idle"] > 0
    assert sum(got["parts"].values()) == pytest.approx(got["idle"])
    # a toy's device waits for its host: most of the idle time has a name
    assert got["parts"]["other"] < 0.2 * got["idle"]
    assert {"mixed", "decode built", "decode ahead"} <= set(got["by_next"])
    read = trace_step_boundary.read
    shares = [read(recorded, part=p) for p in trace_step_boundary.PARTS]
    assert sum(shares) == pytest.approx(100.0)
    assert all(0 <= s <= 100 for s in shares)
    assert 0 <= read(recorded, before="ahead") <= 100
    assert read(recorded, part="clock") == pytest.approx(1e3 * abs(shift))
    assert len(recorded["notes"]) == 1 and "by run_id" in recorded["notes"][0]
    how = spec.load_json("chipbench", "layer_metrics",
                         "mixed_busy_share.tok.json")
    assert 0 < trace_module_share.read(recorded, **how["args"]) < 100
    assert trace_module_p50.read(recorded, **how["args"]) > 0


# ------------------------------------------------------------ the entries ----

def test_the_fifteen_entries_are_appended_with_files_and_readers():
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    at = names.index(NEW[0])
    assert names[at - 1] == "ep8_moe_busy_share.tok"
    assert tuple(names[at:at + len(NEW)]) == NEW and len(NEW) == 15
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    for name in NEW:
        m = by[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "out_tok_s" and m["layer"] in layers
        assert m["workloads"] == (SERVING if name in COUNTERS
                                  else SERVING[:1]), name
        how = spec.load_json("chipbench", "layer_metrics", name + ".json")
        assert set(how) <= {"reader", "args"}
        assert callable(spec.plugin("readers", how["reader"]).read)
        json.dumps(how)
    for name, part in SHARES.items():
        assert spec.load_json("chipbench", "layer_metrics", name + ".json") \
            == {"reader": "trace_step_boundary", "args": {"part": part}}
    # the closed cells report the seven counters and none of the traced eight
    for cell in SERVING[1:]:
        reported = {m["name"] for m in
                    spec.metrics_of(bench, cell, "per_layer")}
        assert set(COUNTERS) <= reported and not set(TRACED) & reported
    assert set(NEW) <= {m["name"] for m in spec.metrics_of(
        bench, SERVING[0], "per_layer")}
    from tests.chipbench.test_chipbench_arith import \
        test_benchmark_json_names_units_and_files as unchanged
    unchanged()


def test_the_counter_entries_read_the_window_summary_and_nothing_else():
    summary = {"adopted_step_share": 0.656, "put_ms_p50": 1.5,
               "speculate_refused_mixed_step_share": 0.3,
               "step_latency_ms_mean": 14.4, "launch_ms_p50": 0.8,
               "front_late_ms_total": 12.5, "front_late_ms_max": 4.0}
    want = {"adopted_step_share.tok": 65.6, "host_put_p50_ms.tok": 1.5,
            "refused_mixed_step_share.tok": 30.0, "step_mean_ms.tok": 14.4,
            "host_launch_p50_ms.tok": 0.8, "front_late_total_ms.tok": 12.5,
            "front_late_max_ms.tok": 4.0}
    for name in COUNTERS:
        how = spec.load_json("chipbench", "layer_metrics", name + ".json")
        assert how["reader"] == "summary_key"
        assert summary_key.read({"summary": summary}, **how["args"]) \
            == pytest.approx(want[name])
        # the parent's summary has no such key: nothing, and no error
        assert summary_key.read({"summary": {"steps": 3}}, **how["args"]) \
            is None
