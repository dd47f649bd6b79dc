"""The cell ``trinity-large-ep8.decode-mixed`` (PR 37), by files and entries
alone: its cut is written down and no width is cut, the pool of two page
groups holds every request to its last token, it runs at its rehearsal sizes
on the CPU and is ``correct``, not with an altered token and not under the
fp8 control; the two opcounts on hand-counted cases; the nine new per-layer
metrics each read a recorded span, scope or counter, and nothing where there
is nothing to read. No chip, no topology."""
import json
import types

import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import ep8_expert_gmm as gmm_count
from chipbench.opcount import windowed_paged_attention as attn_count
from chipbench.readers import summary_key, trace_roofline, trace_scope_share
from chipbench.reference import afmoe as ref

CELL = "trinity-large-ep8.decode-mixed"
NEW = ("win_attn_roofline.tok", "full_attn_roofline.tok",
       "win_attn_busy_share.tok", "full_attn_busy_share.tok",
       "win_fill_mean.tok", "win_pool_occupancy_max.tok",
       "sigmoid_route_busy_share.tok", "ep8_expert_gmm_roofline.tok",
       "ep8_moe_busy_share.tok")
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size", "layer_types"]
KINDS = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _run(seed, seconds=3, **overrides):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run(2 ** 31 + 3737)


def test_the_cut_is_written_down_and_no_width_is_cut():
    bench = spec.benchmark()
    wl, config, traffic = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == entry["reduced"] == REDUCED
    assert {k: v for k, v in config["published"].items()
            if k != "layer_types"} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
        "vocab_size": 200192}
    assert config["published"]["layer_types"] == (KINDS[:4] * 15)
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 32, 25024)
    # the first five of the published list: one dense window layer, then a
    # whole period, three window layers and a global one
    assert config["layer_types"] == KINDS
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # every other number is the source's (the catalog's config, where this
    # sandbox has it; else the values the issue wrote down)
    published = {
        "hidden_size": 3072, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 12288,
        "moe_intermediate_size": 3072, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "sliding_window": 4096,
        "global_attn_every_n_layers": 4, "route_scale": 2.448,
        "route_norm": True, "score_func": "sigmoid", "mup_enabled": True,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "n_group": 1,
        "max_position_embeddings": 262144, "tie_word_embeddings": False}
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
        published = row["config"]
        assert entry["source"] == config["source"] == row["source_url"]
    except OSError:
        pass
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert {"rotary", "qk_norm", "attn_gate", "softmax_scale", "norms",
            "embedding", "router", "weights", "kv_pages",
            "absent_experts"} <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert len(entry["why"]) <= 200
    sz = ref.sizes_of(config)
    assert (sz["held"], sz["experts"], sz["positions"]) == (32, 256, 36992)
    for key in ("sliding_window", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok"):
        assert key in ref.WIDTH_KEYS


@pytest.mark.parametrize("key", [
    "hidden_size", "head_dim", "sliding_window", "intermediate_size",
    "moe_intermediate_size", "num_experts_per_tok", "num_attention_heads",
    "num_key_value_heads"])
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = config["reduced"] + [key]
    config["published"][key] = 1
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


def test_the_traffic_and_the_pool_are_the_issues():
    """32 requests over 32 rows, prompts 1,024 + 896 i, 8,192 out; 10,496
    blocks (a layer's page of one of two groups each) hold every request to
    its last token, so no run preempts however long; every prompt is whole
    chunks of 64. ONE table for all five layers would not fit the chip."""
    from tnn_tpu import models
    from tnn_tpu.serving.kv_pool import PagedKVPool

    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    assert config["program_flags"] == [
        "--model", "trinity_large_ep8", "--block-size", "128",
        "--max-seq-len", "36992", "--num-blocks", "10496",
        "--max-batch-size", "32", "--chunk-size", "64", "--no-prefix-cache"]
    assert config["warmup_prompt_lens"] == [64]
    assert traffic["generator"] == "closed_backlog"
    assert traffic["outstanding"] == traffic["wave"] == 32
    assert sorted(p for p, _ in traffic["requests"]) == [
        1024 + 896 * i for i in range(32)]
    assert [p for p, _ in traffic["requests"]] != sorted(
        p for p, _ in traffic["requests"])      # an order drawn once
    assert {o for _, o in traffic["requests"]} == {8192}
    assert sum(p for p, _ in traffic["requests"]) == 477184
    groups = models.create("trinity_large_ep8").page_groups
    assert groups == dict(window=4096, window_layers=4, full_layers=1)
    pool = PagedKVPool(1, 8, 128, 16, 128, groups=groups)
    assert pool.win_pages == 34 and pool.lane_pack == 1
    need = sum(pool.lifetime_blocks(p + o) for p, o in traffic["requests"])
    assert need == 5776 + 32 * 4 * 34 == 10128 <= 10496 - 1
    assert sum(p + o for p, o in traffic["requests"]) == 739328
    assert max(p + o for p, o in traffic["requests"]) == 36992 \
        == config["served_positions"]
    assert all(p % 64 == 0 for p, _ in traffic["requests"])
    assert pool.table_width(36992) == 289 + 4 * 34 + 1
    # bf16: 0.5 MiB a block; two groups 5.50 GB, one table 15.1 GB
    block = 2 * 8 * 128 * 128 * 2
    assert block == 2 ** 19
    assert round(10496 * block / 1e9, 2) == 5.50
    assert round(5 * 5776 * block / 1e9, 1) == 15.1
    # the four shortest rows cross the window inside a run
    assert sum(p < 4096 for p, _ in traffic["requests"]) == 4


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
    assert 0 < s["win_fill_mean"] <= 1 and s["win_pages_released"] > 0
    assert 0 < s["win_pool_occupancy_max"] < 1


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
    result, obs = _run(2 ** 31 + 3738)
    assert result["correct"] is False
    limits = obs["ctx"].config["rehearsal"]["limits"]
    assert obs["readings"]["gap_max"] > limits["gap_max"]


def test_the_seeded_weights_keep_the_cures():
    """``make_params``: every router column at one norm, the embedding
    small, a selection bias of +-``BIAS`` whose signs come from the seed and
    balance in every block of experts (float32), the norm gains near 1;
    nothing else rescaled."""
    import numpy as np

    config = spec.load_json("chipbench", "configs",
                            "trinity-large-ep8-serve.json")
    sz = ref.sizes_of(config["rehearsal"])
    for seed in (3, 3000000507):
        params = ref.make_params(sz, seed)
        assert "moe" not in params["h0"] and "gate" in params["h0"]
        table = np.asarray(params["wte"]["table"], np.float32)
        assert abs(table.std() - ref.EMBED_STD) < 0.002
        signs = []
        for i in range(1, sz["num_hidden_layers"]):
            moe = params[f"h{i}"]["moe"]
            signs.append(np.sign(np.asarray(moe["expert_bias"])))
            norms = np.linalg.norm(np.asarray(moe["router"], np.float32),
                                   axis=0)
            assert norms.shape == (sz["experts"],)
            assert np.abs(norms - ref.ROUTER_COLUMN_NORM).max() < 4e-3
            router = np.asarray(moe["router"], np.float32)
            assert (router[:, 1::2] == -router[:, 0::2]).all()  # opposed
            post = np.asarray(params[f"h{i}"]["ln1_post"]["scale"],
                              np.float32)
            assert abs(post.mean() - ref.POST_ATTN_GAIN) < 0.01
            assert abs(np.asarray(params[f"h{i}"]["ln2_post"]["scale"],
                                  np.float32).mean() - 1.0) < 0.02
            bias = np.asarray(moe["expert_bias"])
            assert bias.dtype == np.float32 and bias.shape == (16,)
            # +-BIAS, balanced in every block: every chip's share of the
            # experts holds the same biases, whatever the seed
            assert np.allclose(np.abs(bias), ref.BIAS)
            assert not bias.reshape(-1, ref.BIAS_BLOCK).sum(1).any()
            gate = np.linalg.norm(np.asarray(moe["gate"], np.float32),
                                  axis=-1)
            assert gate.std() > 0.02 * gate.mean()
        assert any((a != b).any() for a, b in zip(signs, signs[1:]))


@pytest.mark.parametrize("seed", [3, 3000000507, 3700990404])
def test_no_token_repeats_itself_by_the_heads_draw(seed, monkeypatch):
    """After a run of one token the state is what the token gives alone
    (``alone_forward`` is the reference's own forward of that one token). No
    token's own column stands within ``SELF_MARGIN`` of the best there: the
    columns that did have the opposite sign, they are few, and every other
    column is as it was drawn."""
    import numpy as np

    config = spec.load_json("chipbench", "configs",
                            "trinity-large-ep8-serve.json")
    sz = ref.sizes_of(config["rehearsal"])
    v = sz["vocab_size"]
    params = ref.make_params(sz, seed)
    ids = np.arange(v, dtype=np.int32)
    logits = np.asarray(ref.alone_forward(sz)(params, ids))
    fwd = ref.Forward(params, sz, ref.forward_length(sz, 1))
    for t in (0, v // 3, v - 1):
        assert np.allclose(fwd.rows([t], [0])[0], logits[t], atol=2e-4)
        assert np.allclose(fwd.rows([t] * 40, [39])[0], logits[t], atol=2e-4)
    own = logits[ids, ids]
    others = logits.copy()
    others[ids, ids] = -np.inf
    assert (own <= others.max(1) - ref.SELF_MARGIN + 1e-3).all()
    monkeypatch.setattr(ref, "SELF_MARGIN", -np.inf)    # as drawn
    drawn = np.asarray(ref.make_params(sz, seed)["head"]["kernel"], np.float32)
    kept = np.asarray(params["head"]["kernel"], np.float32)
    turned = (kept != drawn).any(0)
    assert 0 < turned.sum() <= v // 8
    assert (kept[:, turned] == -drawn[:, turned]).all()


# -- the kernels' operations and bytes, on hand-counted cases ---------------

SZ = {"num_hidden_layers": 5, "num_dense_layers": 1, "hidden_size": 3072,
      "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
      "sliding_window": 4096, "layer_types": KINDS,
      "moe_intermediate_size": 3072, "num_experts_per_tok": 4, "held": 32}


def test_attention_decode_work_by_hand():
    """Two decoded tokens over contexts of 1,024 and 28,800. The four window
    layers read 1,024 and 4,096 positions, the global layer all 29,824; a
    position is K and V of 8 x 128 bf16 values = 4,096 bytes, and 48 heads x
    128 x 4 operations."""
    win = attn_count.decode_work([1024, 28800], SZ, "sliding_attention")
    assert win["bytes"] == 4 * (1024 + 4096) * 4096 == 83_886_080
    assert win["flops"] == 4 * (1024 + 4096) * 4 * 48 * 128
    full = attn_count.decode_work([1024, 28800], SZ, "full_attention")
    assert full["bytes"] == 29824 * 4096
    assert full["flops"] / full["bytes"] == 6       # bound by memory
    # a step of the cell's 32 rows at its first token: the window layers'
    # 2.15 GB a step is the ISSUE's, the global layer's grows from 1.96 GB
    prompts = [1024 + 896 * i for i in range(32)]
    assert attn_count.decode_work([4096] * 32, SZ, "sliding_attention")[
        "bytes"] == 2_147_483_648
    assert attn_count.decode_work(prompts, SZ, "full_attention")[
        "bytes"] == 477184 * 4096


def test_expert_step_work_counts_the_four_expert_layers():
    """A step of 32 rows: 128 assignments a layer, an eighth on held
    experts, 12.6 of the 32 held experts hit: the step reads them in each of
    the FOUR expert layers, not in the dense one."""
    work = gmm_count.step_work(SZ, 12.6 / 32, 0.125, 32)
    expert = 3 * 3072 * 3072
    assert work["bytes"] == pytest.approx(4 * 12.6 * expert * 2)
    assert work["bytes"] == pytest.approx(2.854e9, rel=1e-3)
    assert work["flops"] == pytest.approx(4 * 16 * 2 * expert)
    from chipbench.opcount import expert_gmm as old

    five = old.step_work(SZ, 12.6 / 32, 0.125, 32)
    assert five["bytes"] == pytest.approx(work["bytes"] * 5 / 4)


def _obs(ops, summary=None, token_times=(), sizes=SZ):
    """What a traced run leaves the readers: device ops of a recorded slice
    (instruction, scope path, seconds; one after another on one chip), the
    window's counters, and a client whose two requests, of prompts of 1,024
    and 8,192, streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    reqs = {f"r{i}": types.SimpleNamespace(tokens=[0] * n,
                                           token_times=list(token_times))
            for i, n in enumerate((1024, 8192))}
    return {"summary": summary or {}, "sizes": sizes, "trace_meta": meta,
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs=reqs),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step: a window layer's kernel and the
    global layer's under their scopes, the router, the grouped product, the
    shared expert, the page write, the dense layer."""
    ops = [("%tnn_paged_attention_win.3 = bf16[32,8,6,128] custom-call(...)",
            "jit(tnn_serve_decode)/h2/win_attn/paged_attn/pallas_call", 0.20),
           ("%tnn_paged_attention.7 = bf16[32,8,6,128] custom-call(...)",
            "jit(tnn_serve_decode)/h3/full_attn/paged_attn/pallas_call",
            0.10),
           ("%fusion.8 = bf16[32,1,14336] fusion(...)",
            "jit(tnn_serve_decode)/h3/attn_qkv/dot_general", 0.10),
           ("%sort.2 = s32[128] sort(...)",
            "jit(tnn_serve_decode)/h3/moe_route/sort", 0.05),
           ("%tnn_expert_gmm.5 = bf16[640,3072] custom-call(...)",
            "jit(tnn_serve_decode)/h3/moe_experts/pallas_call", 0.35),
           ("%fusion.9 = bf16[32,3072] fusion(...)",
            "jit(tnn_serve_decode)/h3/moe_shared/dot_general", 0.10),
           ("%fusion.10 = bf16[32,1,8,128,128] fusion(...)",
            "jit(tnn_serve_decode)/h3/kv_write/scatter", 0.05),
           ("%fusion.11 = bf16[32,12288] fusion(...)",
            "jit(tnn_serve_decode)/h0/mlp/dot_general", 0.05)]
    return _obs(ops, summary={"expert_held_share": 0.125,
                              "experts_hit_share": 0.375,
                              "win_fill_mean": 0.875,
                              "win_pool_occupancy_max": 0.4},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_nine_new_metrics_read_a_recorded_span_or_counter(recorded):
    assert _read("win_attn_busy_share.tok", recorded) == pytest.approx(20.0)
    assert _read("full_attn_busy_share.tok", recorded) == pytest.approx(10.0)
    assert _read("sigmoid_route_busy_share.tok", recorded) \
        == pytest.approx(5.0)
    assert _read("ep8_moe_busy_share.tok", recorded) == pytest.approx(50.0)
    assert _read("win_fill_mean.tok", recorded) == pytest.approx(87.5)
    assert _read("win_pool_occupancy_max.tok", recorded) \
        == pytest.approx(40.0)
    # each request decoded three tokens in the slice, its 2nd to 4th: the
    # short one over 1,025 to 1,027 positions (all inside the window), the
    # long one over 8,193 to 8,195, of which a window layer reads 4,096
    rows = 4 * ((1025 + 1026 + 1027) + 3 * 4096)
    assert _read("win_attn_roofline.tok", recorded) == pytest.approx(
        100 * rows * 4096 / 819e9 / 0.20)
    rows = (1025 + 1026 + 1027) + (8193 + 8194 + 8195)
    assert _read("full_attn_roofline.tok", recorded) == pytest.approx(
        100 * rows * 4096 / 819e9 / 0.10)
    # three decode steps of 2 rows: 12 experts a layer read in each of FOUR
    least = 3 * 4 * 12 * 3 * 3072 * 3072 * 2 / 819e9
    assert _read("ep8_expert_gmm_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.35)
    # a kernel is found by ITS name: the window kernel's time is not the
    # global one's, nor the other way
    assert trace_roofline.kernel_seconds(
        recorded["trace"], "^tnn_paged_attention_win") == pytest.approx(0.20)
    assert trace_roofline.kernel_seconds(
        recorded["trace"], "^tnn_paged_attention(\\.|$)") \
        == pytest.approx(0.10)


def test_where_there_is_nothing_to_read_the_readers_return_nothing():
    """The parent has no such scope, kernel or counter, and another family's
    sizes no ``layer_types``: every new metric's reader returns None and
    raises nothing, so its line leaves them out."""
    ops = [("%fusion.1 = f32[2] fusion(...)",
            "jit(tnn_serve_decode)/h0/mlp/dot", 1.0)]
    bare = _obs(ops, summary={"batch_fill_mean": 1.0},
                token_times=[10.5, 11.5])
    other = _obs(ops + [("%tnn_paged_attention.1 = bf16[8] custom-call()",
                         "jit(x)/h0/paged_attn/pallas_call", 1.0)],
                 summary={"batch_fill_mean": 1.0}, token_times=[10.5, 11.5],
                 sizes={"n_layer": 36, "n_embd": 1280})
    for name in NEW:
        assert _read(name, bare) is None, name
        assert _read(name, other) is None, name
    assert _read("dense_busy_share.tok", bare) == pytest.approx(100.0)
    assert summary_key.read({}, "win_fill_mean") is None
    assert trace_scope_share.read({}, include="win_attn") is None


def test_the_entries_name_the_new_metrics_and_their_layers():
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
    assert names[at - 1] == "expert_load_max_over_mean.tok"
    assert tuple(names[at:at + len(NEW)]) == NEW
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported
    # the other families' own metrics are not this cell's
    assert not {n for n in reported if n.startswith(
        ("paged_attn", "eva_", "mla_", "expert", "moe_"))}
    # every .tok metric that all three accepted serving cells report
    three = {"gpt2-large.decode", "evabyte-pp2.decode-docs",
             "mistral-small4-ep4.decode-long"}
    generic = {m["name"] for m in bench["per_layer"]
               if three <= set(m.get("workloads", ()))}
    assert len(generic) == 15 and generic <= reported
    assert "hbm_peak_share.tok" in generic
    assert len(reported) == 15 + len(NEW)
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
