"""The cell ``qwen3-next-ep4.decode-state`` (PR 44), by files and entries
alone: its cut is written down and no width is cut, the state slots and the
two full layers' pages hold every request to its last token, it runs at its
rehearsal sizes on the CPU and is ``correct``, not with an altered token and
not under the fp8 control; the three opcounts on hand-counted cases; the
nine new per-layer metrics each read a recorded scope, kernel or counter, and
nothing where there is nothing to read. No chip, no topology."""
import json
import math
import types

import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import qn_expert_gmm as gmm_count
from chipbench.opcount import qn_full_attention as attn_count
from chipbench.opcount import qn_gdn_step as gdn_count
from chipbench.opcount import windowed_paged_attention
from chipbench.readers import summary_key, trace_roofline, trace_scope_share
from chipbench.reference import qwen3_next as ref

CELL = "qwen3-next-ep4.decode-state"
NEW = ("qn_gdn_busy_share.tok", "qn_gdn_step_roofline.tok",
       "qn_full_attn_busy_share.tok", "qn_full_attn_roofline.tok",
       "qn_moe_busy_share.tok", "qn_expert_gmm_roofline.tok",
       "qn_experts_hit_share.tok", "qn_expert_held_share.tok",
       "qn_state_occupancy_max.tok")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SZ = ref.sizes_of(spec.cell(spec.benchmark(), CELL)[1])


def _run(seed, seconds=3, **overrides):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run(2 ** 31 + 4444)


def test_the_cut_is_written_down_and_no_width_is_cut():
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == entry["reduced"] == REDUCED
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 128, 37984)
    assert config["deployment"]["chips_per_layer"] == 4
    assert config["num_experts"] * 4 == 512
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    # the guide's floors: a whole period and 4 layers, 8 experts, 1/8 of
    # the vocabulary; two whole periods here
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0 and config["num_hidden_layers"] >= 4
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert entry["source"] == config["source"] == row["source_url"]
    assert len(row["config"]) >= 25
    for key, value in row["config"].items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert {"state", "delta_net", "decay_init", "full_attention", "router",
            "norms", "embedding", "weights", "kv_pages", "compute_dtype",
            "layer_kinds", "mtp", "absent_experts", "equations"} \
        <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert (SZ["held"], SZ["experts"], SZ["rotary_dim"], SZ["positions"]) \
        == (128, 512, 64, 9216)
    assert SZ["layer_types"] == (["linear_attention"] * 3
                                 + ["full_attention"]) * 2


@pytest.mark.parametrize("key", ref.WIDTH_KEYS)
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = config["reduced"] + [key]
    config["published"][key] = 1
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("rope_scaling", {"factor": 2}),
    ("tie_word_embeddings", True), ("num_experts", 127)])
def test_the_reference_refuses_what_it_does_not_write_out(key, value):
    config = spec.cell(spec.benchmark(), CELL)[1]
    with pytest.raises(ValueError):
        ref.sizes_of(dict(config, **{key: value}))


def test_the_traffic_the_slots_and_the_pool_are_the_issues():
    """80 requests over 80 rows (the issue's 96 less 16: its own remedy for
    a mixed step over 15.2e9 bytes), prompts 512 + 32 i, chunk 32, 6,144
    out; the blocks hold every request to its last token, so no run
    preempts however long; a slot a row; every prompt is whole chunks."""
    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    flags = config["program_flags"]
    get = lambda f: int(flags[flags.index(f) + 1])      # noqa: E731
    rows, blocks, bs, chunk = (get("--max-batch-size"), get("--num-blocks"),
                               get("--block-size"), get("--chunk-size"))
    reqs = traffic["requests"]
    assert traffic["outstanding"] == traffic["wave"] == rows == len(reqs) == 80
    assert sorted(p for p, _ in reqs) == [512 + 32 * i for i in range(80)]
    assert [p for p, _ in reqs] != sorted(p for p, _ in reqs)
    assert {o for _, o in reqs} == {6144}
    assert all(p % chunk == 0 for p, _ in reqs) and chunk == 32
    need = sum(math.ceil((p + o) / bs) for p, o in reqs)
    assert need == 4980 == blocks - 1
    assert max(p + o for p, o in reqs) == 9184 <= get("--max-seq-len") \
        == config["served_positions"]
    assert "--no-prefix-cache" in flags and config["pinned"]["num_blocks"] \
        == blocks and config["pinned"]["max_batch_size"] == rows
    assert config["warmup_prompt_lens"] == [32]
    # three sets of 12.87 MB a row beside the pages and the weights
    state = (3 * rows + 2) * 6 * (48 * 512 * 2 + 32 * 128 * 128 * 4)
    pages = 2 * 2 * blocks * 2 * bs * 256 * 2
    assert 3.0e9 < state < 3.2e9 and 2.6e9 < pages < 2.7e9


def test_the_cell_runs_at_rehearsal_sizes_and_is_correct(served):
    result, obs = served
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    assert result["metrics"] == {}              # no value from a CPU run
    s = obs["summary"]
    assert s["state_restores"] == 0 and s["preemptions"] == 0
    assert s["state_slots_occupancy_max"] == 1.0
    assert 0 < s["experts_hit_share"] <= 1
    assert obs["engine"]["max_batch_size"] == 80


def test_an_altered_token_and_the_fp8_control_are_not_correct(served):
    from chipbench.drivers import serve_stdin_check as chk

    _, obs = served
    limits = obs["ctx"].config["rehearsal"]["limits"]
    sample = chk.sample_requests(obs, 3)
    sound = chk.gap_readings(obs, sample)
    assert sound[0] <= limits["gap_max"] and sound[1] <= limits["gap_mean"]
    low = control.control_readings(obs, "fp8")
    assert low["gap_mean"] > limits["gap_mean"]
    state = chk.gap_readings(obs, sample, control="state_bf16")
    assert state[1] > limits["gap_mean"] or state[0] > limits["gap_max"]
    victim = sample[0]
    kept = list(victim.streamed)
    victim.streamed[5] = (victim.streamed[5] + 1) % 256
    try:
        gmax, _, _ = chk.gap_readings(obs, sample)
    finally:
        victim.streamed[:] = kept
    assert gmax > limits["gap_max"]


# -- the opcounts, by hand ------------------------------------------------------------

def test_state_work_by_hand():
    """A decoded token: in each of 6 linear layers and 32 value heads, a
    state of 128 x 128 float32 read and written, a 16th of it kept, and its
    q, k, v, g, beta."""
    work = gdn_count.token_work(SZ)
    head = 2 * 65536 + 65536 / 16 + (2 * 128 + 128 + 2) * 4
    assert work["bytes"] == 6 * 32 * head
    assert work["bytes"] == pytest.approx(26.3e6, rel=1e-2)
    assert work["flops"] == 6 * 32 * 7 * 16384
    # the cell's 80 rows: 2.1 GB a step
    assert 80 * work["bytes"] == pytest.approx(2.10e9, rel=1e-2)


def test_full_attention_work_by_hand():
    """Decode rows over contexts of 512 and 3,040: K and V of 2 heads of
    256, bf16, in each of the 2 full layers; 16 query heads."""
    work = windowed_paged_attention.decode_work([512, 3040], SZ,
                                                "full_attention")
    assert work["bytes"] == 2 * (512 + 3040) * 2 * 2 * 256 * 2 == 14_548_992
    assert work["flops"] == 2 * (512 + 3040) * 4 * 16 * 256
    assert work["flops"] / work["bytes"] == 8.0


def test_expert_step_work_by_hand():
    """A step of 80 rows: 800 picks a layer, a quarter on held experts, 0.79
    of the 128 held experts hit: the step reads their three weights of 2,048
    x 512 in each of the EIGHT layers."""
    work = gmm_count.step_work(SZ, 0.79, 0.25, 80)
    expert = 3 * 2048 * 512
    assert work["bytes"] == pytest.approx(8 * 0.79 * 128 * expert * 2)
    assert work["bytes"] == pytest.approx(5.09e9, rel=1e-2)
    assert work["flops"] == pytest.approx(8 * 200 * 2 * expert)


def _obs(ops, summary=None, token_times=(), sizes=SZ):
    """What a traced run leaves the readers: device ops of a recorded slice
    (instruction, scope path, seconds; one after another on one chip), the
    window's counters, and a client whose two requests, of prompts of 512
    and 3,040, streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    reqs = {f"r{i}": types.SimpleNamespace(tokens=[0] * n,
                                           token_times=list(token_times))
            for i, n in enumerate((512, 3040))}
    return {"summary": summary or {}, "sizes": sizes, "trace_meta": meta,
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs=reqs),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step: a linear layer (projections, the
    convolution, the state kernel, the output) and a full layer (projections,
    page write, kernel, output), each with its expert layer, the head."""
    step = "jit(tnn_serve_decode)/"
    ops = [("%fusion.1 = bf16[80,1,12288] fusion(...)",
            step + "h0/gdn_proj/dot_general", 0.05),
           ("%fusion.2 = bf16[6,81,48,512] fusion(...)",
            step + "h0/gdn_conv/scatter", 0.02),
           ("%tnn_gdn_step.3 = f32[80,4,8,128] custom-call(...)",
            step + "h0/gdn_state/pallas_call", 0.20),
           ("%fusion.4 = bf16[80,2048] fusion(...)",
            step + "h0/gdn_out/dot_general", 0.03),
           ("%sort.2 = s32[800] sort(...)", step + "h0/moe_route/sort", 0.03),
           ("%tnn_expert_gmm.5 = bf16[2848,2048] custom-call(...)",
            step + "h0/moe_experts/pallas_call", 0.25),
           ("%fusion.6 = f32[80,2048] fusion(...)",
            step + "h0/moe_shared/dot_general", 0.02),
           ("%fusion.7 = bf16[80,9216] fusion(...)",
            step + "h3/attn_qkv/dot_general", 0.04),
           ("%fusion.8 = bf16[2,4981,2,128,256] fusion(...)",
            step + "h3/kv_write/scatter", 0.01),
           ("%tnn_paged_attention.9 = bf16[80,16,256] custom-call(...)",
            step + "h3/full_attn/pallas_call", 0.09),
           ("%fusion.10 = bf16[80,2048] fusion(...)",
            step + "h3/attn_out/dot_general", 0.02),
           ("%tnn_expert_gmm.11 = bf16[2848,2048] custom-call(...)",
            step + "h3/moe_experts/pallas_call", 0.20),
           ("%fusion.12 = f32[80,37984] fusion(...)",
            step + "lm_head/dot_general", 0.04)]
    return _obs(ops, summary={"expert_held_share": 0.25,
                              "experts_hit_share": 0.79,
                              "state_slots_occupancy_max": 1.0},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_nine_new_metrics_read_a_recorded_scope_or_counter(recorded):
    # busy 1.00 s: the linear layer's mixer 0.30, the full layer's page
    # write and kernel 0.10, the expert layers 0.50
    assert _read("qn_gdn_busy_share.tok", recorded) == pytest.approx(30.0)
    assert _read("qn_full_attn_busy_share.tok", recorded) \
        == pytest.approx(10.0)
    assert _read("qn_moe_busy_share.tok", recorded) == pytest.approx(50.0)
    assert _read("qn_experts_hit_share.tok", recorded) == pytest.approx(79.0)
    assert _read("qn_expert_held_share.tok", recorded) == pytest.approx(25.0)
    assert _read("qn_state_occupancy_max.tok", recorded) \
        == pytest.approx(100.0)
    # each request decoded three tokens in the slice
    assert _read("qn_gdn_step_roofline.tok", recorded) == pytest.approx(
        100 * 6 * gdn_count.token_work(SZ)["bytes"] / 819e9 / 0.20)
    # ... its 2nd to 4th, over 513 to 515 and 3,041 to 3,043 positions, in
    # each of the 2 full layers, 2,048 bytes a position a layer
    rows = 2 * ((513 + 514 + 515) + (3041 + 3042 + 3043))
    assert _read("qn_full_attn_roofline.tok", recorded) == pytest.approx(
        100 * rows * 2048 / 819e9 / 0.09)
    # three decode steps of 2 rows: 0.79 of 128 experts a layer, 8 layers
    least = 3 * 8 * 0.79 * 128 * 3 * 2048 * 512 * 2 / 819e9
    assert _read("qn_expert_gmm_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.45)
    # the generic shares read this model's scopes too
    assert _read("kv_write_busy_share.tok", recorded) == pytest.approx(1.0)
    assert _read("unscoped_busy_share.tok", recorded) is None


def test_where_there_is_nothing_to_read_the_readers_return_nothing():
    """The parent has no such scope, kernel or counter, and another family's
    sizes none of this one's keys: every new metric's reader returns None and
    raises nothing, so its line leaves them out."""
    ops = [("%fusion.1 = f32[2] fusion(...)",
            "jit(tnn_serve_decode)/embed/gather", 1.0)]
    bare = _obs(ops, summary={"batch_fill_mean": 1.0},
                token_times=[10.5, 11.5])
    other = _obs(ops + [("%tnn_gdn_step.1 = f32[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0),
                        ("%tnn_paged_attention.3 = bf16[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0),
                        ("%tnn_expert_gmm.2 = bf16[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0)],
                 summary={"batch_fill_mean": 1.0, "experts_hit_share": 0.5,
                          "expert_held_share": 0.25},
                 token_times=[10.5, 11.5],
                 sizes={"num_hidden_layers": 5, "hidden_size": 3072,
                        "layer_types": ["full_attention"] * 5,
                        "num_key_value_heads": 8, "head_dim": 128,
                        "num_attention_heads": 48,
                        "moe_intermediate_size": 3072,
                        "num_experts_per_tok": 4, "held": 32})
    for name in NEW:
        assert _read(name, bare) is None, name
    for name in (NEW[1], NEW[3], NEW[5]):   # another family's run
        assert _read(name, other) is None, name
    assert summary_key.read({}, "state_slots_occupancy_max") is None
    assert trace_scope_share.read({}, include="gdn_state") is None
    assert trace_roofline.read({}, "^tnn_gdn_step", "qn_gdn_step") is None


def test_the_entries_are_appended_together_behind_what_was_there():
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert m["layer"] in layers and m["unit"] == "%"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        json.dumps(spec.load_json("chipbench", "layer_metrics",
                                  name + ".json"))
    # appended behind everything the benchmark had (an entry put in the
    # middle reads as a change to what was there), together and in this
    # order; NOT held to be the last, so a later PR can append behind them
    at = names.index(NEW[0])
    assert names[at - 1] == "attn_query_tile_share.tok"
    assert tuple(names[at:at + len(NEW)]) == NEW
    assert [c["name"] for c in bench["configs"]].index(
        "qwen3-next-ep4-serve") == 6
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 6
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported
    # the other families' own metrics are not this cell's
    assert not {n for n in reported if n.startswith(
        ("paged_attn", "eva_", "mla_", "expert", "moe_", "win_", "full_",
         "ep8_", "sigmoid_", "idle_fetch", "mixed_", "lcf_", "attn_query"))}
    # every .tok metric that PR 41's cell joined, and not the seven that
    # tests/chipbench/test_chipbench_boundary.py holds to exactly four cells
    generic = {m["name"] for m in bench["per_layer"]
               if {"gpt2-large.decode", "longcat-flash-ep32.decode-wide"}
               <= set(m.get("workloads", ()))}
    assert len(generic) == 15 and generic <= reported
    assert len(reported) == 15 + len(NEW)
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
