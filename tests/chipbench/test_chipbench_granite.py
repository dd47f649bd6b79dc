"""The cell ``granite4-h-micro.decode-ssm`` (PR 49), by files and entries
alone: NOTHING is cut (every published key at its published value, ``reduced``
empty, the whole vocabulary), the state slots and the four attention layers'
pages hold every request to its last token, it runs at its rehearsal sizes on
the CPU and is ``correct``, not with an altered token and not under the fp8
control; the two opcounts on hand-counted cases; the six new per-layer
metrics each read a recorded scope, kernel or counter, and nothing where
there is nothing to read. No chip, no topology."""
import json
import math
import types

import numpy as np
import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import g4_attention as attn_count
from chipbench.opcount import g4_mamba2_step as ssm_count
from chipbench.readers import arithmetic, summary_key, trace_roofline, \
    trace_scope_share
from chipbench.reference import granite_hybrid as ref

CELL = "granite4-h-micro.decode-ssm"
NEW = ("g4_ssm_step_roofline.tok", "g4_ssm_busy_share.tok",
       "g4_attn_roofline.tok", "g4_attn_busy_share.tok",
       "g4_state_occupancy_max.tok", "g4_state_hbm_share.tok")
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
    return _run(2 ** 31 + 4949)


def test_nothing_is_cut_and_every_published_key_stands():
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == entry["reduced"] == []
    assert "published" not in config and "deployment" not in config
    assert "one replica" in config["deployment_note"]
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
    assert entry["source"] == config["source"] == row["source_url"]
    assert len(row["config"]) >= 33
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert {"state", "in_proj_order", "gate_and_norm", "conv", "step", "norms",
            "multipliers", "positions", "feed_forward", "decay_init",
            "embedding", "weights", "kv_pages", "compute_dtype", "residual",
            "equations", "mamba_chunk_size"} <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert len(entry["why"]) <= 200
    assert (SZ["positions"], SZ["head_dim"], SZ["vocab_size"]) \
        == (9600, 64, 100352)
    assert [i for i, k in enumerate(SZ["layer_types"]) if k == "attention"] \
        == [5, 15, 25, 35]


def test_sizes_of_counts_the_whole_model():
    """3,191.4 M parameters, by the reference's own shapes: 36 Mamba layers
    of 76.18 M, 4 attention layers of 60.82 M, the tied table and the final
    norm."""
    def count(tree):
        return sum(int(np.prod(s)) for s in _leaves(tree))

    shapes = ref.param_shapes(SZ)
    assert count(shapes) == 3_191_396_096
    assert count(shapes["h0"]) == 76_182_976
    assert count(shapes["h0"]["attn"]) == 25_847_232
    assert count(shapes["h5"]) == 60_821_504
    assert count(shapes["h5"]["attn"]) == 10_485_760
    assert count(shapes["wte"]) + count(shapes["ln_f"]) == 205_522_944
    assert 1.4e-3 < ref.embed_std(SZ) < 1.6e-3
    assert ref.forward_length(SZ, 9600) == 10240


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


@pytest.mark.parametrize("key", ref.WIDTH_KEYS)
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = [key]
    config["published"] = {key: 1}
    config.setdefault(key, 1)
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


@pytest.mark.parametrize("key,value", [
    ("position_embedding_type", "rope"), ("num_local_experts", 8),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("attention_bias", True), ("tie_word_embeddings", False),
    ("mamba_n_groups", 8), ("mamba_expand", 4), ("hidden_act", "gelu"),
    ("layer_types", ["mamba"] * 39), ("rope_scaling", {"factor": 2})])
def test_the_reference_refuses_what_it_does_not_write_out(key, value):
    config = spec.cell(spec.benchmark(), CELL)[1]
    with pytest.raises(ValueError):
        ref.sizes_of(dict(config, **{key: value}))


def test_the_traffic_the_slots_and_the_pool_are_the_issues():
    """24 requests over 24 rows, prompts 512 + 128 i in an order drawn once,
    chunk 64, 6,144 out; the blocks hold every request to its last token, so
    no run preempts however long; a slot a row; every prompt is whole
    chunks; the state arrays are the pool's largest tenant."""
    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    flags = config["program_flags"]
    get = lambda f: int(flags[flags.index(f) + 1])      # noqa: E731
    rows, blocks, bs, chunk = (get("--max-batch-size"), get("--num-blocks"),
                               get("--block-size"), get("--chunk-size"))
    reqs = traffic["requests"]
    assert traffic["outstanding"] == traffic["wave"] == rows == len(reqs) == 24
    order = [int(i) for i in np.random.default_rng(49).permutation(24)]
    assert [p for p, _ in reqs] == [512 + 128 * i for i in order]
    assert sum(p for p, _ in reqs) == 47616 and {o for _, o in reqs} == {6144}
    assert all(p % chunk == 0 for p, _ in reqs) and chunk == 64
    need = sum(math.ceil((p + o) / bs) for p, o in reqs)
    assert need == 1524 == blocks - 1
    assert max(p + o for p, o in reqs) == 9600 == get("--max-seq-len") \
        == config["served_positions"]
    assert "--no-prefix-cache" in flags and config["pinned"]["num_blocks"] \
        == blocks and config["pinned"]["max_batch_size"] == rows
    assert config["warmup_prompt_lens"] == [64]
    # three sets of 76.44 MB a row beside the pages and the weights
    one = 36 * (102 * 128 * 2 + 64 * 64 * 128 * 4)
    assert one == 76_437_504
    state = (3 * rows + 2) * one
    pages = 2 * 4 * blocks * 8 * bs * 64 * 2
    assert 5.6e9 < state < 5.7e9 and 1.59e9 < pages < 1.61e9
    assert state > pages and 13.6e9 < state + pages + 2 * 3_191_396_096 \
        < 13.7e9


def test_the_cell_runs_at_rehearsal_sizes_and_is_correct(served):
    result, obs = served
    assert result["correct"] and result["rehearsal"] and not result["failed"]
    assert result["metrics"] == {}              # no value from a CPU run
    s = obs["summary"]
    assert s["preemptions"] == 0 and s["state_slots_occupancy_max"] == 1.0
    # 4 state layers x (25 live + 49 snapshot slots) x (3 x 160 + 8 x 16 x
    # 16) float32 values
    assert s["state_bytes"] == 4 * 74 * (480 + 2048) * 4
    assert obs["facts"]["summary.state_bytes"] == s["state_bytes"]
    assert obs["engine"]["max_batch_size"] == 24
    assert "experts_hit_share" not in s and "win_fill_mean" not in s


def test_an_altered_token_and_the_fp8_control_are_not_correct(served):
    from chipbench.drivers import serve_stdin_check as chk

    _, obs = served
    limits = obs["ctx"].config["rehearsal"]["limits"]
    sample = chk.sample_requests(obs, 3)
    sound = chk.gap_readings(obs, sample)
    assert sound[0] <= limits["gap_max"] and sound[1] <= limits["gap_mean"]
    for precision in ("fp8", "int8"):
        low = control.control_readings(obs, precision)
        assert low["gap_mean"] > limits["gap_mean"]
        assert low["gap_max"] > limits["gap_max"]
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
    """A decoded token: in each of 36 Mamba layers and 64 heads, a state of
    64 x 128 float32 read and written, a 16th of it kept, and its x, step and
    decay; B and C once a layer."""
    work = ssm_count.token_work(SZ)
    head = 2 * 32768 + 32768 / 16 + (64 + 2) * 4
    assert work["bytes"] == 36 * (64 * head + 2 * 128 * 4)
    # the issue's arithmetic: 36 x 64 x (2 x 32,768 + a 16th) = 155.7 MB,
    # and the small operands beside it
    assert 36 * 64 * (65536 + 2048) == 155_713_536
    assert work["bytes"] == pytest.approx(155.7e6, rel=5e-3)
    assert work["flops"] == 36 * 64 * 5 * 8192
    # the cell's 24 rows: 3.75 GB a step, 36% of what a step moves
    assert 24 * work["bytes"] == pytest.approx(3.75e9, rel=1e-2)


def test_attention_work_by_hand():
    """Decode rows over contexts of 512 and 3,456: K and V of 8 heads of 64,
    bf16, in each of the 4 attention layers; 32 query heads."""
    from chipbench.opcount import windowed_paged_attention

    work = windowed_paged_attention.decode_work([512, 3456], SZ, "attention")
    assert work["bytes"] == 4 * (512 + 3456) * 2 * 8 * 64 * 2 == 32_505_856
    assert work["bytes"] == (512 + 3456) * 8192
    assert work["flops"] == 4 * (512 + 3456) * 4 * 32 * 64
    assert work["flops"] / work["bytes"] == 4.0


def _obs(ops, summary=None, token_times=(), sizes=SZ, facts=None):
    """What a traced run leaves the readers: device ops of a recorded slice
    (instruction, scope path, seconds; one after another on one chip), the
    window's counters, and a client whose two requests, of prompts of 512
    and 3,456, streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    reqs = {f"r{i}": types.SimpleNamespace(tokens=[0] * n,
                                           token_times=list(token_times))
            for i, n in enumerate((512, 3456))}
    return {"summary": summary or {}, "sizes": sizes, "trace_meta": meta,
            "facts": facts or {},
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs=reqs),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step: a Mamba layer (projection, the
    convolution, the state kernel, the output), an attention layer
    (projections, page write, kernel, output), their feed-forwards, the
    head."""
    step = "jit(tnn_serve_decode)/"
    ops = [("%fusion.1 = bf16[24,1,8512] fusion(...)",
            step + "h0/ssm_proj/dot_general", 0.10),
           ("%fusion.2 = bf16[36,25,102,128] fusion(...)",
            step + "h0/ssm_conv/scatter", 0.02),
           ("%tnn_mamba2_step.3 = f32[24,4,64,16] custom-call(...)",
            step + "h0/ssm_state/pallas_call", 0.25),
           ("%fusion.4 = bf16[24,2048] fusion(...)",
            step + "h0/ssm_out/dot_general", 0.05),
           ("%fusion.5 = f32[24,2048] fusion(...)",
            step + "h0/mlp/dot_general", 0.30),
           ("%fusion.7 = bf16[24,3072] fusion(...)",
            step + "h5/attn_qkv/dot_general", 0.03),
           ("%tnn_kv_row_write.8 = bf16[4,1525,4,128,128] custom-call(...)",
            step + "h5/kv_write/pallas_call", 0.01),
           ("%tnn_paged_attention.9 = bf16[24,32,64] custom-call(...)",
            step + "h5/paged_attn/pallas_call", 0.07),
           ("%fusion.10 = bf16[24,2048] fusion(...)",
            step + "h5/attn_out/dot_general", 0.02),
           ("%fusion.11 = f32[24,2048] fusion(...)",
            step + "h5/mlp/dot_general", 0.10),
           ("%fusion.12 = f32[24,100352] fusion(...)",
            step + "lm_head/dot_general", 0.05)]
    return _obs(ops, summary={"state_slots_occupancy_max": 1.0,
                              "state_bytes": 5.6e9},
                facts={"summary.state_bytes": 5.6e9, "peak.hbm_bytes": 16e9},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_six_new_metrics_read_a_recorded_scope_or_counter(recorded):
    # busy 1.00 s: the Mamba layer's mixer 0.42, the attention layer's page
    # write and kernel 0.08
    assert _read("g4_ssm_busy_share.tok", recorded) == pytest.approx(42.0)
    assert _read("g4_attn_busy_share.tok", recorded) == pytest.approx(8.0)
    assert _read("g4_state_occupancy_max.tok", recorded) \
        == pytest.approx(100.0)
    assert _read("g4_state_hbm_share.tok", recorded) == pytest.approx(35.0)
    # each request decoded three tokens in the slice
    assert _read("g4_ssm_step_roofline.tok", recorded) == pytest.approx(
        100 * 6 * ssm_count.token_work(SZ)["bytes"] / 819e9 / 0.25)
    # ... its 2nd to 4th, over 513 to 515 and 3,457 to 3,459 positions, in
    # each of the 4 attention layers, 2,048 bytes a position a layer
    rows = (513 + 514 + 515) + (3457 + 3458 + 3459)
    assert _read("g4_attn_roofline.tok", recorded) == pytest.approx(
        100 * rows * 8192 / 819e9 / 0.07)
    # the generic shares read this model's scopes too
    assert _read("kv_write_busy_share.tok", recorded) == pytest.approx(1.0)
    assert _read("dense_busy_share.tok", recorded) == pytest.approx(50.0)
    assert _read("unscoped_busy_share.tok", recorded) is None
    # Qwen3-Next's own find nothing in this model's run
    for name in ("qn_gdn_step_roofline.tok", "qn_full_attn_roofline.tok",
                 "qn_gdn_busy_share.tok"):
        assert _read(name, recorded) is None, name


def test_where_there_is_nothing_to_read_the_readers_return_nothing():
    """The parent has no such scope, kernel or counter, and another family's
    sizes none of this one's keys: every new metric's reader returns None and
    raises nothing, so its line leaves them out."""
    ops = [("%fusion.1 = f32[2] fusion(...)",
            "jit(tnn_serve_decode)/embed/gather", 1.0)]
    bare = _obs(ops, summary={"batch_fill_mean": 1.0},
                facts={"peak.hbm_bytes": 16e9}, token_times=[10.5, 11.5])
    other = _obs(ops + [("%tnn_mamba2_step.1 = f32[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0),
                        ("%tnn_paged_attention.3 = bf16[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0)],
                 summary={"batch_fill_mean": 1.0},
                 token_times=[10.5, 11.5],
                 sizes={"num_hidden_layers": 8, "hidden_size": 2048,
                        "layer_types": ["linear_attention"] * 6
                        + ["full_attention"] * 2,
                        "num_key_value_heads": 2, "head_dim": 256,
                        "num_attention_heads": 16,
                        "full_attention_interval": 4,
                        "linear_num_value_heads": 32})
    for name in NEW:
        assert _read(name, bare) is None, name
    for name in (NEW[0], NEW[2]):           # another family's run
        assert _read(name, other) is None, name
    assert summary_key.read({}, "state_slots_occupancy_max") is None
    assert arithmetic.read({"facts": {}}, "summary.state_bytes",
                           "peak.hbm_bytes") is None
    assert trace_scope_share.read({}, include="ssm_state") is None
    assert trace_roofline.read({}, "^tnn_mamba2_step", "g4_mamba2_step") \
        is None


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
    assert [by[n]["source"] for n in NEW] == ["device_trace"] * 4 \
        + ["program_counter"] * 2
    # appended behind everything the benchmark had (an entry put in the
    # middle reads as a change to what was there), together and in this
    # order; NOT held to be the last, so a later PR can append behind them
    at = names.index(NEW[0])
    assert names[at - 1] == "qn_state_occupancy_max.tok"
    assert tuple(names[at:at + len(NEW)]) == NEW
    assert [c["name"] for c in bench["configs"]].index(
        "granite4-h-micro-serve") == 7
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 7
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported
    # the other families' own metrics are not this cell's
    assert not {n for n in reported if n.startswith(
        ("paged_attn", "eva_", "mla_", "expert", "moe_", "win_", "full_",
         "ep8_", "sigmoid_", "idle_fetch", "mixed_", "lcf_", "attn_query",
         "qn_"))}
    # every .tok metric that PR 44's cell joined, and not the seven that
    # tests/chipbench/test_chipbench_boundary.py holds to exactly four cells
    generic = {m["name"] for m in bench["per_layer"]
               if {"gpt2-large.decode", "qwen3-next-ep4.decode-state"}
               <= set(m.get("workloads", ()))}
    assert len(generic) == 15 and generic <= reported
    assert len(reported) == 15 + len(NEW)
    for name in generic:                    # appended behind PR 44's cell
        cells = by[name]["workloads"]
        assert cells.index(CELL) == cells.index(
            "qwen3-next-ep4.decode-state") + 1, name
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
    assert len(bench["workloads"]) == 8 == len(bench["configs"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
