"""The readers PR 24 adds, on the CPU: the wire-format reading of a recorded
trace (scope paths, programs, host spans), each reader's value by hand, and
what a trace with no device plane gives. No chip, no network, no topology."""
import json
import os
import types

import pytest

from chipbench import spec
from chipbench.readers import (trace_host_span_p50, trace_idle_named,
                               trace_module_p50, trace_scope_share)
from chipbench.reduce import xplane, xplane_meta

REDUCE = os.path.join(spec.ROOT, "chipbench", "reduce")
SAMPLE = os.path.join(REDUCE, "sample_v5e.xplane.pb")
SCOPED = os.path.join(REDUCE, "sample_v5e_scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return {"trace_meta": xplane_meta.read_file(SCOPED),
            "ctx": types.SimpleNamespace(note=lambda msg: None)}


# ------------------------------------------------ PR 23's recorded trace ----

def test_meta_recovers_scope_paths_of_the_first_sample():
    meta = xplane_meta.read_file(SAMPLE)
    assert meta["chips"] == 1 and len(meta["ops"]) == 36
    by_name = {trace_scope_share.instruction(o["name"]): o
               for o in meta["ops"]}
    fusion = by_name["convolution_tanh_fusion"]
    assert fusion["tf_op"] == "jit(tiny)/dot_general"
    assert fusion["category"] == "convolution fusion"
    kernel = by_name["tiny.1"]      # named after the function around it:
    assert kernel["tf_op"] == "jit(tiny)/pallas_call"       # no name= yet
    assert by_name["copy"]["tf_op"].split(";")[0] == "jit(tiny)/reshape"
    assert {m["name"].split("(")[0] for m in meta["modules"]} == {"jit_tiny"}
    assert len(meta["modules"]) == 3 and meta["spans"] == []


@pytest.mark.parametrize("path", [SAMPLE, SCOPED])
def test_meta_agrees_with_the_profile_data_reduction(path):
    meta, old = xplane_meta.read_file(path), xplane.reduce_file(path)
    assert xplane_meta.busy_seconds(meta) == pytest.approx(old["busy_s"],
                                                           rel=2e-3)
    assert sum(o["dur"] for o in meta["ops"]) == pytest.approx(
        sum(s for _, s, _ in old["ops"]), rel=2e-3)
    assert len(meta["ops"]) == sum(n for _, _, n in old["ops"])


# ------------------------------------ the scoped trace (PR 24, a v5e) ----
# record_sample.py: three runs of jit(tnn_sample) = a matmul under
# ``attn_qkv`` (1.855, 2.136, 1.853 us) and the kernel ``tnn_sample_kernel``
# under ``paged_attn`` (1.014, 1.009, 1.010 us), a prefetch copy pair with no
# path before each (0.017, 0.018, 0.018 us), busy 8.928 us in all.

def test_scoped_sample_names_are_the_programs(scoped):
    meta = scoped["trace_meta"]
    assert meta["chips"] == 1 and len(meta["ops"]) == 12
    paths = {trace_scope_share.instruction(o["name"]).split(".")[0]:
             o["tf_op"] for o in meta["ops"]}
    assert paths == {
        "copy-start": "", "copy-done": "",
        "convolution_tanh_fusion": "jit(tnn_sample)/attn_qkv/dot_general",
        "tnn_sample_kernel":        # the kernel's own name, not its caller's
        "jit(tnn_sample)/paged_attn/tnn_sample_kernel/pallas_call"}
    assert [m["name"].split("(")[0] for m in meta["modules"]] == \
        ["jit_tnn_sample"] * 3
    threads = {s["thread"] for s in meta["spans"]}
    assert len(threads) == 1 and len(meta["spans"]) == 12
    first = sorted(meta["spans"], key=lambda s: s["start"])[:4]
    assert [(s["name"], s["stats"]) for s in first] == [
        ("serve.build", {"step": 0}), ("serve.admit", {"rid": 0, "step": 0}),
        ("serve.dispatch", {"step": 0, "kind": "decode", "key": "sample"}),
        ("serve.fetch", {"step": 0})]


def test_readers_on_the_scoped_sample(scoped):
    busy = 8.92773
    assert xplane_meta.busy_seconds(scoped["trace_meta"]) * 1e6 == \
        pytest.approx(busy, rel=1e-5)
    read = trace_scope_share.read
    assert read(scoped, include=r"\battn_qkv\b") == pytest.approx(
        100 * (1.855 + 2.136 + 1.853) / busy, rel=1e-3)
    assert read(scoped, include=r"\bpaged_attn\b") == pytest.approx(
        100 * (1.014 + 1.009 + 1.010) / busy, rel=1e-3)
    assert read(scoped, include="^tnn_sample_kernel", by="name") == \
        read(scoped, include=r"\bpaged_attn\b")
    assert read(scoped, exclude=r"\b(attn_qkv|paged_attn)\b") == \
        pytest.approx(100 * 0.053 / busy, rel=0.05)
    assert read(scoped, include=r"\bkv_write\b") is None
    assert trace_module_p50.read(scoped, pattern=r"^jit_tnn_sample\b") == \
        pytest.approx(2.896e-3, rel=1e-3)
    assert trace_module_p50.read(scoped, pattern="^jit_tiny") is None
    assert trace_host_span_p50.read(scoped, span="serve.dispatch") == \
        pytest.approx(0.33775, rel=1e-4)
    assert trace_host_span_p50.read(scoped, span="serve.commit") is None
    # idle: two gaps between the three programs, 8.997 ms; every instant to
    # the innermost span (the admit inside the build), the sleeps to nobody
    named, idle = trace_idle_named.idle_by_span(scoped["trace_meta"])
    assert idle * 1e3 == pytest.approx(8.99746, rel=1e-4)
    assert {k: round(v * 1e3, 3) for k, v in named.items()} == {
        "serve.admit": 2.224, "serve.build": 2.235, "serve.dispatch": 0.563,
        "serve.fetch": 1.120}
    assert trace_idle_named.read(scoped) == pytest.approx(
        100 * 6.1422 / 8.99746, rel=1e-3)


# ------------------------------------------------- readers, by hand ----

def _obs(ops=(), modules=(), spans=()):
    """A run's observations with a hand-made trace: ops are (name, tf_op,
    start ms, ms), modules (name, ms), spans (thread, name, start ms, ms)."""
    notes = []
    meta = {
        "chips": 1 if ops else 0,
        "ops": [{"name": f"%{n} = f32[] op()", "tf_op": t, "category": "",
                 "chip": "0", "start": s / 1e3, "dur": d / 1e3}
                for n, t, s, d in ops],
        "modules": [{"name": n, "start": 0.0, "dur": d / 1e3, "chip": "0"}
                    for n, d in modules],
        "spans": [{"thread": th, "name": n, "start": s / 1e3, "dur": d / 1e3,
                   "stats": {}} for th, n, s, d in spans]}
    return {"trace_meta": meta, "notes": notes,
            "ctx": types.SimpleNamespace(note=notes.append)}


OPS = [
    ("fusion.1", "jit(tnn_serve_decode)/h0/attn_qkv/dot_general", 0, 2),
    ("copy.7", "jit(tnn_serve_decode)/h0/kv_write/scatter", 2, 10),
    ("tnn_paged_attention.3",
     "jit(tnn_serve_decode)/h0/paged_attn/tnn_paged_attention/pallas_call",
     12, 4),
    ("copy.9", "pages_k", 16, 3),
    ("copy-done.2", "", 20, 1),         # 1 ms idle before it, no scope
    ("fusion.8", "jit(tnn_train_step)/transpose(jvp(h1))/mlp/dot_general",
     30, 5),                            # 9 ms idle before it
]


def test_scope_share_by_hand():
    obs = _obs(OPS)                     # busy 25 ms
    read = trace_scope_share.read
    assert read(obs, include=r"\bkv_write\b") == pytest.approx(40.0)
    assert read(obs, include=r"\bkv_write\b|^pages_[kv]\b") == \
        pytest.approx(52.0)
    assert read(obs, include=r"\b(attn_qkv|mlp)\b") == pytest.approx(28.0)
    assert read(obs, include="^tnn_paged_attention", by="name") == \
        pytest.approx(16.0)
    catalog = spec.load_json("chipbench", "layer_metrics",
                             "unscoped_busy_share.tok.json")["args"]
    assert read(obs, **catalog) == pytest.approx(4.0)   # copy-done alone
    assert read(obs, include=r"\bflash_attn\b") is None     # nothing there
    # overlapping ops: shares are of BUSY time (the union), as PR 23's are
    both = _obs(OPS + [("fusion.2", "jit(f)/h0/mlp/add", 0, 2)])
    assert read(both, include=r"\bmlp\b") == pytest.approx(28.0)


def test_module_p50_by_hand():
    obs = _obs(OPS, modules=[("jit_tnn_serve_decode(123)", 640.0),
                             ("jit_tnn_serve_decode(123)", 642.0),
                             ("jit_tnn_serve_decode(123)", 700.0),
                             ("jit_tnn_serve_decode_fused(9)", 5.0),
                             ("jit_tnn_serve_mixed_w64(77)", 690.0)])
    how = spec.load_json("chipbench", "layer_metrics",
                         "decode_step_device_p50_ms.tok.json")
    assert how["reader"] == "trace_module_p50"
    assert trace_module_p50.read(obs, **how["args"]) == pytest.approx(642.0)
    assert trace_module_p50.read(obs, pattern="^jit_tnn_train_step") is None


SPANS = [
    ("engine", "serve.fetch", 0, 19.5),     # gap 19-20: 0.5 in, 0.5 out
    ("engine", "serve.commit", 19.5, 0.25),
    ("engine", "serve.build", 21, 5),       # gap 21-30 (9 ms): 5 here ...
    ("engine", "serve.admit", 22, 1),       # ... of which 1 is the admit's
    ("engine", "serve.dispatch", 26, 2),    # ... 2 here, 2 unnamed
    ("main", "front.read", 0, 50),          # another thread: not the driver
    ("engine", "serve.build", 40, 3),
]


def test_idle_named_and_host_span_by_hand():
    obs = _obs(OPS, spans=SPANS)
    segs = trace_idle_named.innermost(
        [s for s in obs["trace_meta"]["spans"] if s["thread"] == "engine"])
    assert [(round(a * 1e3, 2), round(b * 1e3, 2), n) for a, b, n in segs] \
        == [(0, 19.5, "serve.fetch"), (19.5, 19.75, "serve.commit"),
            (21, 22, "serve.build"), (22, 23, "serve.admit"),
            (23, 26, "serve.build"), (26, 28, "serve.dispatch"),
            (40, 43, "serve.build")]
    named, idle = trace_idle_named.idle_by_span(obs["trace_meta"])
    assert idle == pytest.approx(10e-3)
    assert {k: round(v * 1e3, 3) for k, v in named.items()} == {
        "serve.fetch": 0.5, "serve.commit": 0.25, "serve.build": 4.0,
        "serve.admit": 1.0, "serve.dispatch": 2.0}
    assert trace_idle_named.read(obs) == pytest.approx(77.5)
    assert "serve.build 4.000" in obs["notes"][0]       # the gap table
    assert trace_host_span_p50.read(obs, span="serve.build") == \
        pytest.approx(4.0)                              # median of 5 and 3
    assert trace_host_span_p50.read(obs, span="train.input") is None
    # idle from before the thread's first recorded span does not count (a
    # span that was open when the recording began is not in the trace)
    assert trace_idle_named.read(_obs(OPS, spans=SPANS[2:])) == \
        pytest.approx(100 * 7 / 9)
    # spans but no dispatch span (or none at all): nothing to attribute to
    assert trace_idle_named.read(_obs(OPS, spans=SPANS[:2])) is None
    assert trace_idle_named.read(_obs(OPS)) is None


def test_every_reader_gives_none_without_a_device_plane(tmp_path):
    """A rehearsal's trace: host spans, no ``/device:TPU`` plane."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("train.input", rows=8):
            with jax.profiler.TraceAnnotation("train.dispatch"):
                pass
    finally:
        jax.profiler.stop_trace()
    obs = {"ctx": types.SimpleNamespace(trace_dir=str(tmp_path),
                                        note=lambda m: None)}
    meta = xplane_meta.of(obs)
    assert meta["chips"] == 0 and meta["ops"] == [] == meta["modules"]
    spans = {s["name"]: s for s in meta["spans"]}
    assert spans["train.input"]["stats"] == {"rows": 8}
    assert trace_scope_share.read(obs, include="mlp") is None
    assert trace_scope_share.read(obs, exclude="mlp") is None
    assert trace_module_p50.read(obs, pattern=".") is None
    assert trace_idle_named.read(obs) is None
    assert trace_host_span_p50.read(obs, span="train.input") >= 0.0
    assert trace_host_span_p50.read(obs, span="serve.build") is None
    # and a run that was not traced at all
    bare = {"ctx": types.SimpleNamespace(trace_dir=None)}
    assert xplane_meta.of(bare) is None
    assert trace_scope_share.read(bare, include="mlp") is None
    assert trace_idle_named.read(bare) is None


@pytest.mark.parametrize("tf_op,want", [
    ("jit(tnn_serve_decode)/h3/kv_write/scatter", "kv_write"),
    ("jit(tnn_train_step)/transpose(jvp(h3))/mlp/dot_general", "mlp"),
    ("jit(tnn_train_step)/jvp(loss)/reduce_sum", "loss"),
    ("jit(tnn_train_step)/jvp(loss)/jit(log_softmax)/log", "loss"),
    ("jit(f)/h0/paged_attn/tnn_paged_attention/pallas_call",
     "tnn_paged_attention"),
    ("jit(tnn_train_step)/optimizer/grad_clip/sqrt", "grad_clip"),
    ("jit(f)/h0/squeeze;jit(f)/h0/attn_qkv/reshape", "attn_qkv"),
    ("jit(tnn_train_step)/cos", "(none)"),
    ("jit(tnn_train_step)/jvp(h0)/attn_qkv/transpose", "attn_qkv"),
    ("jit(tnn_train_step)/transpose(jvp(h3))/add_any",
     "h* (a block, no inner scope)"),
    ("pages_k", "pages_k"),
    ("", "(none)"),
])
def test_leaf_scope(tf_op, want):
    assert xplane_meta.leaf_scope(tf_op) == want


# ------------------------------------------------------- the entries ----

def test_new_metrics_are_entries_and_files_alone():
    """What PR 24 appended: each metric one JSON file naming a reader that
    exists, `layer` one of the benchmark's own, in a cell that reports the
    metric it moves (test_benchmark_json_names_units_and_files, unchanged,
    checks the rest)."""
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("kv_write_busy_share.tok")
    assert names.index("device_idle_share.train") == first - 1
    layers = {m["layer"] for m in bench["per_layer"][:first]}
    added = bench["per_layer"][first:]
    assert len(added) == 15
    for m in added:
        assert m["layer"] in layers, m["name"]
        how = spec.load_json("chipbench", "layer_metrics",
                             m["name"] + ".json")
        assert set(how) <= {"reader", "args"}
        assert callable(spec.plugin("readers", how["reader"]).read)
    from tests.chipbench.test_chipbench_arith import \
        test_benchmark_json_names_units_and_files as unchanged
    unchanged()
