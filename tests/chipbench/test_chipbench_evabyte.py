"""The cell ``evabyte-pp2.decode-docs`` (PR 28), by files and entries alone:
its cut is written down, it runs at its rehearsal sizes on the CPU and is
``correct``, not with an altered token and not under the fp8 control; the
kernel's operation count on a hand-counted case; the five new per-layer
metrics each read a recorded span, scope or counter. No chip, no topology."""
import json
import types

import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import eva_attention as opcount
from chipbench.readers import summary_key, trace_roofline, trace_scope_share
from chipbench.reference import evabyte as ref

CELL = "evabyte-pp2.decode-docs"
NEW = ("eva_attn_roofline.tok", "eva_attn_busy_share.tok",
       "eva_summary_busy_share.tok", "eva_window_fill_mean.tok",
       "eva_summary_rows_max.tok")


def _run(seed, seconds=3, **overrides):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run(2 ** 31 + 2828)


def test_the_cut_is_written_down_and_only_the_depth_is_cut():
    bench = spec.benchmark()
    wl, config, traffic = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == ["num_hidden_layers"] == entry["reduced"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 16
    assert config["deployment"]["chips_per_layer"] == 1
    catalog = {"hidden_size": 4096, "intermediate_size": 11008,
               "num_attention_heads": 32, "num_key_value_heads": 32,
               "vocab_size": 320, "max_position_embeddings": 32768,
               "window_size": 2048, "chunk_size": 16, "num_pred_heads": 8,
               "rope_theta": 100000, "rms_norm_eps": 1e-05,
               "norm_add_unit_offset": True, "fp32_skip_add": True,
               "attention_class": "eva", "tie_word_embeddings": False}
    assert {k: config[k] for k in catalog} == catalog
    assert {"equations", "phi_mu", "first_read", "phi_scale", "pred_heads",
            "weights", "kv_pages"} <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert traffic["requests"] == [
        [15616, 8192], [6400, 8192], [22528, 8192], [11008, 8192],
        [17920, 8192], [8704, 8192], [20224, 8192], [13312, 8192]]
    assert traffic["outstanding"] == traffic["wave"] == 8
    assert traffic["generator"] == "closed_backlog"
    # the eight prompts start at eight phases of the window, 256 apart
    assert sorted(p % 2048 for p, _ in traffic["requests"]) == list(
        range(0, 2048, 256))
    assert max(p + o for p, o in traffic["requests"]) <= 32768


@pytest.mark.parametrize("key", ["hidden_size", "window_size", "chunk_size",
                                 "num_pred_heads", "intermediate_size"])
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = ["num_hidden_layers", key]
    config["published"][key] = 1
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


def test_the_pool_is_sized_to_every_requests_last_token():
    """``program_flags``: 224 blocks hold the eight requests to their last
    token (admission holds them to that), so a run never preempts."""
    from tnn_tpu.serving.kv_pool import PagedKVPool

    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    flags = dict(zip(config["program_flags"][::2],
                     config["program_flags"][1::2]))
    pool = PagedKVPool(1, 1, 8, int(flags["--num-blocks"]),
                       int(flags["--block-size"]),
                       window=config["window_size"],
                       chunk=config["chunk_size"])
    need = sum(pool.lifetime_blocks(p + o) for p, o in traffic["requests"])
    assert need == 220 <= pool.capacity == 223
    assert pool.table_width(int(flags["--max-seq-len"])) == 32
    assert int(flags["--max-batch-size"]) == traffic["wave"]
    assert all(p % int(flags["--chunk-size"]) == 0
               for p, _ in traffic["requests"])


def test_the_cell_runs_by_files_and_entries_alone_and_is_correct(served):
    result, obs = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert obs["engine"]["decode_path"] == "paged"
    assert obs["readings"]["tokens"] >= 50
    s = obs["summary"]
    assert s["preemptions"] == 0 and s["eva_windows_rolled"] >= 1
    assert 0 < s["eva_window_fill_mean"] <= 1
    assert 0 < s["eva_summary_rows_max"] <= 1


def test_the_fp8_control_fails_the_comparison(served):
    _, obs = served
    limits = obs["ctx"].config["rehearsal"]["limits"]
    low = control.control_readings(obs)
    assert low["gap_max"] > limits["gap_max"] \
        or low["gap_mean"] > limits["gap_mean"], (low, limits)
    assert low["gap_mean"] > 3 * obs["readings"]["gap_mean"]


def test_an_altered_token_makes_the_run_incorrect(monkeypatch):
    from tnn_tpu.serving.supervisor import EngineSupervisor

    real = EngineSupervisor._emit

    def emit(self, rid, ev):
        if ev.get("event") == "token":
            ev = dict(ev, token=(int(ev["token"]) + 7) % 320)
        return real(self, rid, ev)

    monkeypatch.setattr(EngineSupervisor, "_emit", emit)
    result, obs = _run(2 ** 31 + 2829)
    assert result["correct"] is False
    limits = obs["ctx"].config["rehearsal"]["limits"]
    assert obs["readings"]["gap_max"] > limits["gap_max"]


# -- the kernel's operations and bytes, on a hand-counted case ---------------

SZ = {"window_size": 2048, "chunk_size": 16, "hidden_size": 4096,
      "num_hidden_layers": 16}


@pytest.mark.parametrize("position,exact,summaries", [
    (0, 1, 0), (2047, 2048, 0), (2048, 1, 128), (6400, 257, 384),
    (30719, 2048, 1792)])
def test_rows_read(position, exact, summaries):
    assert opcount.rows_read(position, SZ) == (exact, summaries)


def test_decode_work_by_hand():
    """Two decoded tokens, at positions 2,048 (1 exact row + 128 summaries)
    and 6,400 (257 + 384): 770 rows; K and V of 4,096 values a row, 16
    layers, 2 bytes a value; 4 FLOPs a value read."""
    work = opcount.decode_work([2048, 6400], SZ)
    assert work["bytes"] == 770 * 2 * 4096 * 16 * 2 == 201_850_880
    assert work["flops"] == 4 * 770 * 2 * 4096 * 16


def _obs(ops, summary=None, token_times=()):
    """What a traced run leaves the readers: device ops of a recorded
    slice (instruction, scope path, seconds; one after another on one chip),
    the window's counters, and a client whose one request, of a prompt of
    6,400, streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    req = types.SimpleNamespace(tokens=[0] * 6400,
                                token_times=list(token_times))
    return {"summary": summary or {}, "sizes": SZ, "trace_meta": meta,
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs={"r0": req}),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step: the EVA kernel under its scope,
    the summary write under its own, the exact rows' write, another
    program's kernel, a matmul."""
    ops = [("%tnn_eva_attention.3 = bf16[8,32,1,128] custom-call(...)",
            "jit(tnn_serve_decode)/h3/eva_attn/pallas_call", 0.40),
           ("%fusion.9 = bf16[8,1,32,128,128] fusion(...)",
            "jit(tnn_serve_decode)/h3/eva_summarise/scatter", 0.05),
           ("%fusion.10 = bf16[8,1,32,128,128] fusion(...)",
            "jit(tnn_serve_decode)/h3/kv_write/scatter", 0.05),
           ("%tnn_paged_attention.1 = bf16[8,32,1,128] custom-call(...)",
            "jit(other)/h0/paged_attn/pallas_call", 0.10),
           ("%fusion.11 = bf16[8,11008] fusion(...)",
            "jit(tnn_serve_decode)/h3/mlp/dot_general", 0.40)]
    return _obs(ops, summary={"eva_window_fill_mean": 0.5,
                              "eva_summary_rows_max": 0.29,
                              "eva_windows_rolled": 3},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_five_new_metrics_read_a_recorded_span_or_counter(recorded):
    assert _read("eva_attn_busy_share.tok", recorded) == pytest.approx(40.0)
    assert _read("eva_summary_busy_share.tok", recorded) == pytest.approx(5.0)
    assert _read("eva_window_fill_mean.tok", recorded) == pytest.approx(50.0)
    assert _read("eva_summary_rows_max.tok", recorded) == pytest.approx(29.0)
    # three decoded tokens in the slice, the request's 2nd to 4th, at
    # positions 6,400 to 6,402: 257..259 exact rows and 384 summaries each
    rows = sum(e + 384 for e in (257, 258, 259))
    least = rows * 2 * 4096 * 16 * 2 / 819e9
    assert _read("eva_attn_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.40)
    # the kernel is found by ITS name: the other kernel's time is not its
    assert trace_roofline.kernel_seconds(
        recorded["trace"], "^tnn_eva_attention") == pytest.approx(0.40)


def test_where_there_is_nothing_to_read_the_readers_return_nothing():
    """The parent has no such scope, kernel or counter: every new metric's
    reader returns None and raises nothing, so its line leaves them out."""
    bare = _obs([("%fusion.1 = f32[2] fusion(...)",
                  "jit(tnn_serve_decode)/h0/mlp/dot", 1.0)],
                summary={"batch_fill_mean": 1.0},
                token_times=[10.5, 11.5])
    for name in NEW:
        assert _read(name, bare) is None, name
    assert _read("dense_busy_share.tok", bare) == pytest.approx(100.0)
    assert summary_key.read({}, "eva_window_fill_mean") is None
    assert trace_scope_share.read({}, include="eva_attn") is None


def test_the_entries_name_the_new_metrics_and_their_layers():
    bench = spec.benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert m["layer"] in layers and m["unit"] == "%"
        json.dumps(spec.load_json("chipbench", "layer_metrics",
                                  name + ".json"))
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported and "paged_attn_roofline.tok" not in reported
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
