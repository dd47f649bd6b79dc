"""The cell ``mistral-small4-ep4.decode-long`` (PR 32), by files and entries
alone: its cut is written down and no width is cut, the pool holds every
request to its last token, it runs at its rehearsal sizes on the CPU and is
``correct``, not with an altered token and not under the fp8 control; the
two kernels' operation counts on hand-counted cases; the eight new per-layer
metrics each read a recorded span, scope or counter. No chip, no topology."""
import json
import types

import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import expert_gmm as gmm_count
from chipbench.opcount import mla_attention as mla_count
from chipbench.readers import summary_key, trace_roofline, trace_scope_share
from chipbench.reference import mistral4 as ref

CELL = "mistral-small4-ep4.decode-long"
NEW = ("mla_attn_roofline.tok", "mla_attn_busy_share.tok",
       "expert_gmm_roofline.tok", "moe_busy_share.tok",
       "moe_route_busy_share.tok", "expert_held_share.tok",
       "experts_hit_share.tok", "expert_load_max_over_mean.tok")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _run(seed, seconds=3, **overrides):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run(2 ** 31 + 3232)


def test_the_cut_is_written_down_and_no_width_is_cut():
    bench = spec.benchmark()
    wl, config, traffic = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 36,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 32, 32768)
    assert config["deployment"]["chips_per_layer"] == 4
    # every other number is the source's (the catalog's config, where this
    # sandbox has it; else the values the issue wrote down)
    published = {
        "hidden_size": 4096, "num_attention_heads": 32, "q_lora_rank": 1024,
        "kv_lora_rank": 256, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "first_k_dense_replace": 0, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "max_position_embeddings": 1048576, "tie_word_embeddings": False}
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        published = next(r["config"] for r in rows
                         if r["name"] == "Mistral-Small-4-119B-2603")
    except OSError:
        pass
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["rope_parameters"]["factor"] == 128
    assert config["rope_parameters"]["llama_4_scaling_beta"] == 0.1
    assert {"gate", "softmax_scale", "query_scaling", "vision_tower",
            "weights", "kv_pages", "absent_experts"} <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert len(entry["why"]) <= 200
    sz = ref.sizes_of(config)
    assert (sz["held"], sz["experts"], sz["positions"]) == (32, 128, 32768)


@pytest.mark.parametrize("key", [
    "hidden_size", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "num_experts_per_tok",
    "num_attention_heads"])
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = config["reduced"] + [key]
    config["published"][key] = 1
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


def test_the_traffic_and_the_pool_are_the_issues():
    """32 requests over 32 rows, prompts 8,192 + 512 i, 8,192 out; 6,144
    latent pages hold every request to its last token, so no run preempts
    however long; every prompt is whole chunks of 64."""
    from tnn_tpu.serving.kv_pool import PagedKVPool

    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    assert config["program_flags"] == [
        "--model", "mistral_small4", "--block-size", "128", "--max-seq-len",
        "32768", "--num-blocks", "6144", "--max-batch-size", "32",
        "--chunk-size", "64", "--no-prefix-cache"]
    assert config["warmup_prompt_lens"] == [64]
    assert traffic["generator"] == "closed_backlog"
    assert traffic["outstanding"] == traffic["wave"] == 32
    assert sorted(p for p, _ in traffic["requests"]) == [
        8192 + 512 * i for i in range(32)]
    assert [p for p, _ in traffic["requests"]] != sorted(
        p for p, _ in traffic["requests"])      # an order drawn once
    assert {o for _, o in traffic["requests"]} == {8192}
    assert sum(p for p, _ in traffic["requests"]) == 516096
    pool = PagedKVPool(1, 1, 384, 6144, 128, latent=True)
    need = sum(pool.lifetime_blocks(p + o) for p, o in traffic["requests"])
    assert need == 6080 <= pool.capacity == 6143
    assert sum(p + o for p, o in traffic["requests"]) == 778240
    assert max(p + o for p, o in traffic["requests"]) <= 32768
    assert all(p % 64 == 0 for p, _ in traffic["requests"])
    assert pool.page_shape == (1, 6144, 1, 128, 384)
    assert pool.kv_bytes_per_token == 384 * 4       # one array (f32 here)


def test_the_cell_runs_by_files_and_entries_alone_and_is_correct(served):
    result, obs = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert obs["engine"]["decode_path"] == "paged"
    assert obs["readings"]["tokens"] >= 50
    s = obs["summary"]
    assert s["preemptions"] == 0
    assert 0.2 < s["expert_held_share"] < 0.8
    assert 0 < s["experts_hit_share"] <= 1
    assert s["expert_load_max_over_mean"] >= 1


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
            ev = dict(ev, token=(int(ev["token"]) + 7) % 256)
        return real(self, rid, ev)

    monkeypatch.setattr(EngineSupervisor, "_emit", emit)
    result, obs = _run(2 ** 31 + 3233)
    assert result["correct"] is False
    limits = obs["ctx"].config["rehearsal"]["limits"]
    assert obs["readings"]["gap_max"] > limits["gap_max"]


# -- the kernels' operations and bytes, on hand-counted cases ---------------

SZ = {"num_hidden_layers": 6, "hidden_size": 4096, "num_attention_heads": 32,
      "kv_lora_rank": 256, "qk_rope_head_dim": 64,
      "moe_intermediate_size": 2048, "num_experts_per_tok": 4, "held": 32}


def test_a_routers_columns_have_one_norm():
    """``make_params`` gives every expert's router column the same norm, so
    that no seed's draw makes the held experts more or less popular than a
    quarter (``reference/mistral4.ROUTER_COLUMN_NORM``): on two seeds the
    norms agree to bfloat16's rounding, and nothing else is rescaled."""
    import numpy as np

    config = spec.load_json("chipbench", "configs",
                            "mistral-small4-ep4-serve.json")
    sz = ref.sizes_of(config["rehearsal"])
    for seed in (3, 3000000507):
        params = ref.make_params(sz, seed)
        for i in range(sz["num_hidden_layers"]):
            moe = params[f"h{i}"]["moe"]
            norms = np.linalg.norm(np.asarray(moe["router"], np.float32),
                                   axis=0)
            assert norms.shape == (sz["experts"],)
            assert np.abs(norms - ref.ROUTER_COLUMN_NORM).max() < 4e-3
            gate = np.linalg.norm(np.asarray(moe["gate"], np.float32),
                                  axis=-1)
            assert gate.std() > 0.02 * gate.mean()


def test_mla_decode_work_by_hand():
    """Two decoded tokens over contexts of 8,192 and 24,064: 32,256 latent
    rows a layer, each read ONCE: 320 values of 2 bytes; every head
    multiplies the row (320) for its score and its first 256 values for its
    output: 2 * 32 * 576 operations a row."""
    work = mla_count.decode_work([8192, 24064], SZ)
    assert work["bytes"] == 32256 * 6 * 320 * 2 == 123_863_040
    assert work["flops"] == 32256 * 6 * 2 * 32 * (320 + 256)
    assert work["flops"] / work["bytes"] == pytest.approx(57.6)


def test_expert_step_work_by_hand():
    """A step of 32 rows: 128 assignments a layer, a quarter on held
    experts, 20 of the 32 held experts hit: the step reads 20 experts of 3 *
    4,096 * 2,048 bf16 values in each of 6 layers, and makes 32 assignments'
    6 * 4,096 * 2,048 operations."""
    work = gmm_count.step_work(SZ, 20 / 32, 0.25, 32)
    assert work["bytes"] == 6 * 20 * 3 * 4096 * 2048 * 2 == 6_039_797_760
    assert work["flops"] == 6 * 32 * 6 * 4096 * 2048
    assert work["flops"] / work["bytes"] < 2        # bound by memory


def _obs(ops, summary=None, token_times=()):
    """What a traced run leaves the readers: device ops of a recorded slice
    (instruction, scope path, seconds; one after another on one chip), the
    window's counters, and a client whose two requests, of prompts of 8,192,
    streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    reqs = {f"r{i}": types.SimpleNamespace(tokens=[0] * 8192,
                                           token_times=list(token_times))
            for i in range(2)}
    return {"summary": summary or {}, "sizes": SZ, "trace_meta": meta,
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs=reqs),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step: the latent kernel and the absorbed
    query under ``mla_attn`` / ``attn_qkv``, the router, the grouped product,
    the shared expert, the page write, another program's kernel."""
    ops = [("%tnn_mla_attention.3 = bf16[32,1,32,256] custom-call(...)",
            "jit(tnn_serve_decode)/h3/mla_attn/pallas_call", 0.20),
           ("%fusion.8 = bf16[32,1,32,384] fusion(...)",
            "jit(tnn_serve_decode)/h3/attn_qkv/dot_general", 0.05),
           ("%sort.2 = s32[128] sort(...)",
            "jit(tnn_serve_decode)/h3/moe_route/sort", 0.05),
           ("%tnn_expert_gmm.5 = bf16[640,4096] custom-call(...)",
            "jit(tnn_serve_decode)/h3/moe_experts/pallas_call", 0.40),
           ("%fusion.9 = bf16[32,4096] fusion(...)",
            "jit(tnn_serve_decode)/h3/moe_shared/dot_general", 0.05),
           ("%fusion.10 = bf16[32,1,1,128,384] fusion(...)",
            "jit(tnn_serve_decode)/h3/kv_write/scatter", 0.05),
           ("%tnn_paged_attention.1 = bf16[8,32,1,128] custom-call(...)",
            "jit(other)/h0/paged_attn/pallas_call", 0.20)]
    return _obs(ops, summary={"expert_held_share": 0.25,
                              "experts_hit_share": 0.625,
                              "expert_load_max_over_mean": 4.0},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_eight_new_metrics_read_a_recorded_span_or_counter(recorded):
    assert _read("mla_attn_busy_share.tok", recorded) == pytest.approx(20.0)
    assert _read("moe_busy_share.tok", recorded) == pytest.approx(50.0)
    assert _read("moe_route_busy_share.tok", recorded) == pytest.approx(5.0)
    assert _read("expert_held_share.tok", recorded) == pytest.approx(25.0)
    assert _read("experts_hit_share.tok", recorded) == pytest.approx(62.5)
    assert _read("expert_load_max_over_mean.tok", recorded) \
        == pytest.approx(4.0)
    # each request decoded three tokens in the slice, its 2nd to 4th, over
    # contexts of 8,193 to 8,195
    rows = 2 * (8193 + 8194 + 8195)
    least = rows * 6 * 320 * 2 / 819e9
    assert _read("mla_attn_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.20)
    # three decode steps of 2 rows: 20 experts a layer read in each
    least = 3 * 6 * 20 * 3 * 4096 * 2048 * 2 / 819e9
    assert _read("expert_gmm_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.40)
    # a kernel is found by ITS name: the other kernels' time is not its
    assert trace_roofline.kernel_seconds(
        recorded["trace"], "^tnn_mla_attention") == pytest.approx(0.20)
    assert trace_roofline.kernel_seconds(
        recorded["trace"], "^tnn_expert_gmm") == pytest.approx(0.40)


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
    assert summary_key.read({}, "experts_hit_share") is None
    assert trace_scope_share.read({}, include="moe_route") is None


def test_the_entries_name_the_new_metrics_and_their_layers():
    bench = spec.benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert m["layer"] in layers
        json.dumps(spec.load_json("chipbench", "layer_metrics",
                                  name + ".json"))
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported
    assert not {n for n in reported if n.startswith(("paged_attn", "eva_"))}
    # every .tok metric that both accepted serving cells report, this one too
    both = {m["name"] for m in bench["per_layer"]
            if {"gpt2-large.decode", "evabyte-pp2.decode-docs"}
            <= set(m.get("workloads", ()))}
    assert both <= reported and "hbm_peak_share.tok" in both
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
