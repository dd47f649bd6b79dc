"""The cell ``longcat-flash-ep32.decode-wide`` (PR 41), by files and entries
alone: its cut is written down and no width is cut, the latent pool of eight
cache layers holds every request to its last token, it runs at its rehearsal
sizes on the CPU and is ``correct``, not with an altered token and not under
the fp8 control; the two opcounts on hand-counted cases; the ten new
per-layer metrics each read a recorded scope, kernel or counter, and nothing
where there is nothing to read. No chip, no topology."""
import json
import types

import pytest

from chipbench import control, spec
from chipbench import run as bench_run
from chipbench.opcount import lcf_expert_gmm as gmm_count
from chipbench.opcount import lcf_mla_attention as attn_count
from chipbench.readers import summary_key, trace_roofline, trace_scope_share
from chipbench.reference import longcat_flash as ref

CELL = "longcat-flash-ep32.decode-wide"
NEW = ("lcf_mla_attn_roofline.tok", "lcf_expert_gmm_roofline.tok",
       "lcf_mla_busy_share.tok", "lcf_dense_ffn_busy_share.tok",
       "lcf_moe_busy_share.tok", "lcf_route_busy_share.tok",
       "lcf_zero_pick_share.tok", "lcf_ffn_picks_per_token_mean.tok",
       "lcf_experts_hit_share.tok", "lcf_expert_held_share.tok")
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _run(seed, seconds=3, **overrides):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run(2 ** 31 + 4141)


def test_the_cut_is_written_down_and_no_width_is_cut():
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = spec.by_name(bench["configs"], wl["config"], "configuration")
    spec.check_cut(entry, config, ref)
    assert config["reduced"] == entry["reduced"] == REDUCED
    assert config["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert config["deployment"]["chips_per_layer"] == 32
    assert config["n_routed_experts"] * 32 == 512
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # the guide's floors: 4 layers, 8 experts, 1/8 of the vocabulary
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8
    # every other number is the source's (the catalog's config, where this
    # sandbox has it; else the values the issue wrote down)
    published = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "LongCat-Flash-Omni")
        published = row["config"]
        assert entry["source"] == config["source"] == row["source_url"]
    except OSError:
        pass
    assert len(published) >= 20
    for key, value in published.items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert {"block", "rotary", "rank_scales", "softmax_scale", "router",
            "zero_experts", "norms", "embedding", "weights", "kv_pages",
            "compute_dtype", "omni_towers", "absent_experts"} \
        <= set(config["assumed"])
    assert wl["chips"] == 1 and len(wl["why"]) <= 200
    assert len(entry["why"]) <= 200
    sz = ref.sizes_of(config)
    assert (sz["held"], sz["experts"], sz["zero_expert_num"],
            sz["positions"]) == (16, 512, 256, 8192)
    assert ref.rank_scales(sz) == (2.0, 12 ** 0.5)


@pytest.mark.parametrize("key", [
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "moe_topk",
    "zero_expert_num"])
def test_a_cut_of_a_width_is_refused(key):
    bench = spec.benchmark()
    wl, config, _ = spec.cell(bench, CELL)
    entry = dict(spec.by_name(bench["configs"], wl["config"], "c"))
    entry["reduced"] = config["reduced"] = config["reduced"] + [key]
    config["published"][key] = 1
    with pytest.raises(ValueError, match="no width is ever cut"):
        spec.check_cut(entry, config, ref)


@pytest.mark.parametrize("key,value", [
    ("mla_scale_q_lora", False), ("mla_scale_kv_lora", False),
    ("zero_expert_type", "zero"), ("attention_method", "MHA"),
    ("attention_bias", True), ("n_routed_experts", 15)])
def test_the_reference_refuses_what_it_does_not_write_out(key, value):
    config = spec.cell(spec.benchmark(), CELL)[1]
    with pytest.raises(ValueError):
        ref.sizes_of(dict(config, **{key: value}))


def test_the_traffic_and_the_pool_are_the_issues():
    """64 requests over 64 rows, prompts 256 + 32 i, the issue's chunk of
    32; 4,608 out and 3,024 blocks (a page of all EIGHT cache layers each):
    ONE step of 512 and its 256 pages under the issue's 5,120 and 3,280, its
    own remedy for a program over 15.2e9 bytes. The blocks hold every request
    to its last token, so no run preempts however long; every prompt is
    whole chunks."""
    import jax.numpy as jnp

    from tnn_tpu import models
    from tnn_tpu.serving.kv_pool import PagedKVPool

    _, config, traffic = spec.cell(spec.benchmark(), CELL)
    assert config["program_flags"] == [
        "--model", "longcat_flash_ep32", "--block-size", "128",
        "--max-seq-len", "8192", "--num-blocks", "3024",
        "--max-batch-size", "64", "--chunk-size", "32", "--no-prefix-cache"]
    assert config["warmup_prompt_lens"] == [32]
    assert traffic["generator"] == "closed_backlog"
    assert traffic["outstanding"] == traffic["wave"] == 64
    assert sorted(p for p, _ in traffic["requests"]) == [
        256 + 32 * i for i in range(64)]
    assert [p for p, _ in traffic["requests"]] != sorted(
        p for p, _ in traffic["requests"])      # an order drawn once
    assert {o for _, o in traffic["requests"]} == {5120 - 512}
    assert 3024 == 3280 - 64 * 512 // 128
    assert sum(p for p, _ in traffic["requests"]) == 80896
    assert all(p % 32 == 0 for p, _ in traffic["requests"])
    model = models.create("longcat_flash_ep32")
    assert (model.cache_layers, model.latent_row) == (8, 640)
    pool = PagedKVPool(model.cache_layers, 1, model.latent_row, 16, 128,
                       dtype=jnp.bfloat16, latent=True)
    assert pool.page_shape == (8, 16, 1, 128, 640)
    need = sum(pool.lifetime_blocks(p + o) for p, o in traffic["requests"])
    assert need == 64 * 38 + 528 == 2960 <= 3024 - 1
    assert max(p + o for p, o in traffic["requests"]) == 6880 \
        <= config["served_positions"] == 8192
    # bf16: a row of 640 lanes in each of 8 cache layers a token
    assert pool.kv_bytes_per_token == 8 * 640 * 2 == 10240
    block = 128 * pool.kv_bytes_per_token
    assert block == 1_310_720 and round(3024 * block / 1e9, 2) == 3.96
    # no request can end in a 51 s window: 4,608 steps would be 11.07 ms
    # each, and a step reads over 9 GB
    assert 51.0 / 4608 < 9.2e9 / 819e9


def test_the_cell_runs_by_files_and_entries_alone_and_is_correct(served):
    result, obs = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert obs["engine"]["decode_path"] == "paged"
    assert obs["readings"]["tokens"] >= 50
    s = obs["summary"]
    assert s["preemptions"] == 0
    assert 0.15 < s["expert_held_share"] < 0.5      # 8 of 24
    assert 0.2 < s["zero_pick_share"] < 0.45        # 8 of 24
    assert 3.3 < s["ffn_picks_per_token_mean"] < 4.8
    assert s["ffn_picks_max_over_mean"] >= 1
    assert 0 < s["experts_hit_share"] <= 1


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
    result, obs = _run(2 ** 31 + 4142)
    assert result["correct"] is False
    limits = obs["ctx"].config["rehearsal"]["limits"]
    assert obs["readings"]["gap_max"] > limits["gap_max"]


def test_the_seeded_weights_keep_the_cures():
    """``make_params``: every router column at one norm and in opposed
    pairs over ALL the router's ids, the embedding small, a selection bias of
    +-``BIAS`` whose signs come from the seed and balance in every block of
    ids (float32), the norm gains near 1."""
    import numpy as np

    config = spec.load_json("chipbench", "configs",
                            "longcat-flash-ep32-serve.json")
    sz = ref.sizes_of(config["rehearsal"])
    width = sz["experts"] + sz["zero_expert_num"]
    for seed in (3, 3000000507):
        params = ref.make_params(sz, seed)
        table = np.asarray(params["wte"]["table"], np.float32)
        assert abs(table.std() - ref.EMBED_STD) < 0.002
        signs = []
        for i in range(sz["num_layers"]):
            blk = params[f"h{i}"]
            assert set(blk) == {"a0", "a1", "moe"}
            moe = blk["moe"]
            assert set(moe) == {"router", "expert_bias", "gate", "up",
                                "down"}            # no shared expert
            signs.append(np.sign(np.asarray(moe["expert_bias"])))
            router = np.asarray(moe["router"], np.float32)
            assert router.shape == (sz["hidden_size"], width)
            assert np.abs(np.linalg.norm(router, axis=0)
                          - ref.ROUTER_COLUMN_NORM).max() < 6e-3
            assert (router[:, 1::2] == -router[:, 0::2]).all()  # opposed
            bias = np.asarray(moe["expert_bias"])
            assert bias.dtype == np.float32 and bias.shape == (width,)
            assert np.allclose(np.abs(bias), ref.BIAS)
            assert not bias.reshape(-1, ref.BIAS_BLOCK).sum(1).any()
            assert moe["gate"].shape == (sz["held"], 32, 64)
            for half in ("a0", "a1"):
                for norm in ("ln1", "ln2"):
                    gain = np.asarray(blk[half][norm]["scale"], np.float32)
                    assert abs(gain.mean() - 1.0) < 0.02 and gain.std() > 0
                assert blk[half]["gate"]["kernel"].shape == (64, 128)
        assert any((a != b).any() for a, b in zip(signs, signs[1:]))
        a0 = np.asarray(params["h0"]["a0"]["attn"]["q_a_kernel"], np.float32)
        a1 = np.asarray(params["h0"]["a1"]["attn"]["q_a_kernel"], np.float32)
        assert (a0 != a1).any()         # two attentions, two sets of weights


@pytest.mark.parametrize("seed", [3, 3000000507, 3700990404])
def test_no_token_repeats_itself_by_the_heads_draw(seed, monkeypatch):
    """After a run of one token the state is what the token gives alone
    (``alone_forward`` is the reference's own forward of that one token). No
    token's own column stands within ``SELF_MARGIN`` of the best there: the
    columns that did have the opposite sign, they are few, and every other
    column is as it was drawn."""
    import numpy as np

    config = spec.load_json("chipbench", "configs",
                            "longcat-flash-ep32-serve.json")
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
    assert turned.sum() <= v // 8
    assert (kept[:, turned] == -drawn[:, turned]).all()


# -- the kernels' operations and bytes, on hand-counted cases ---------------

SZ = {"num_layers": 4, "hidden_size": 6144, "num_attention_heads": 64,
      "kv_lora_rank": 512, "qk_rope_head_dim": 64,
      "expert_ffn_hidden_size": 2048, "moe_topk": 12, "zero_expert_num": 256,
      "held": 16}


def test_latent_attention_decode_work_by_hand():
    """Two decoded tokens over contexts of 256 and 2,272: each reads its
    context's rows of 576 live bf16 values once in each of EIGHT cache layers
    (two a block), and 64 heads multiply a row of 576 for the score and its
    first 512 values for the output."""
    work = attn_count.decode_work([256, 2272], SZ)
    assert work["bytes"] == 8 * (256 + 2272) * 576 * 2 == 23_298_048
    assert work["flops"] == 8 * (256 + 2272) * 2 * 64 * (576 + 512)
    assert work["flops"] / work["bytes"] == pytest.approx(120.9, rel=1e-3)
    assert attn_count.cache_layers(SZ) == 8
    # the cell's 64 rows at their first token: 0.75 GB a step at live values
    # (0.83 GB at the 640 lanes a row takes in the pool)
    prompts = [256 + 32 * i for i in range(64)]
    assert attn_count.decode_work(prompts, SZ)["bytes"] \
        == 8 * 80896 * 1152 == 745_537_536
    from chipbench.opcount import mla_attention as old

    assert old.decode_work([256], dict(SZ, num_hidden_layers=8)) \
        == attn_count.decode_work([256], SZ)


def test_expert_step_work_by_hand():
    """A step of 64 rows: 768 picks a layer, 16 / 768 of them on held
    experts, 10.2 of the 16 held experts hit: the step reads their three
    weights of 6,144 x 2,048 in each of the FOUR expert layers (one a
    block)."""
    work = gmm_count.step_work(SZ, 10.2 / 16, 16 / 768, 64)
    expert = 3 * 6144 * 2048
    assert work["bytes"] == pytest.approx(4 * 10.2 * expert * 2)
    assert work["bytes"] == pytest.approx(3.08e9, rel=1e-2)
    assert work["flops"] == pytest.approx(4 * 16 * 2 * expert)  # 16 picks
    mapped = gmm_count.as_expert_gmm(SZ)
    assert (mapped["num_hidden_layers"], mapped["moe_intermediate_size"],
            mapped["num_experts_per_tok"]) == (4, 2048, 12)


def _obs(ops, summary=None, token_times=(), sizes=SZ):
    """What a traced run leaves the readers: device ops of a recorded slice
    (instruction, scope path, seconds; one after another on one chip), the
    window's counters, and a client whose two requests, of prompts of 256
    and 2,272, streamed tokens at ``token_times``."""
    meta = {"chips": 1, "modules": [], "spans": [], "ops": [
        {"name": n, "tf_op": t, "dur": d, "chip": 0,
         "start": sum(x[2] for x in ops[:i])}
        for i, (n, t, d) in enumerate(ops)]}
    reqs = {f"r{i}": types.SimpleNamespace(tokens=[0] * n,
                                           token_times=list(token_times))
            for i, n in enumerate((256, 2272))}
    return {"summary": summary or {}, "sizes": sizes, "trace_meta": meta,
            "ctx": types.SimpleNamespace(trace_wall=(10.0, 13.0)),
            "client": types.SimpleNamespace(reqs=reqs),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"ops": [(n, d, 1) for n, _, d in ops],
                      "window_s": 3.0}}


@pytest.fixture
def recorded():
    """A recorded slice of a decode step of one block: both attentions
    (projections, page write, kernel, output), both dense feed-forwards, the
    router, the grouped product, the identity term and the shortcut's add,
    the head."""
    step = "jit(tnn_serve_decode)/h1/"
    ops = [("%fusion.1 = bf16[64,1,1536] fusion(...)",
            step + "a0/attn_qkv/dot_general", 0.04),
           ("%fusion.2 = bf16[8,3024,1,128,640] fusion(...)",
            step + "a0/kv_write/scatter", 0.01),
           ("%tnn_mla_attention.3 = bf16[64,64,512] custom-call(...)",
            step + "a0/mla_attn/pallas_call", 0.08),
           ("%fusion.4 = bf16[64,6144] fusion(...)",
            step + "a0/attn_out/dot_general", 0.02),
           ("%sort.2 = s32[768] sort(...)",
            step + "a0/moe_route/sort", 0.02),
           ("%tnn_expert_gmm.5 = bf16[1024,6144] custom-call(...)",
            step + "a0/moe_experts/pallas_call", 0.25),
           ("%fusion.6 = f32[64,6144] fusion(...)",
            step + "a0/moe_zero/mul", 0.01),
           ("%fusion.7 = bf16[64,12288] fusion(...)",
            step + "a0/mlp/dot_general", 0.17),
           ("%tnn_mla_attention.8 = bf16[64,64,512] custom-call(...)",
            step + "a1/mla_attn/pallas_call", 0.12),
           ("%fusion.9 = bf16[64,6144] fusion(...)",
            step + "a1/attn_out/dot_general", 0.03),
           ("%fusion.10 = bf16[64,12288] fusion(...)",
            step + "a1/mlp/dot_general", 0.18),
           ("%fusion.11 = f32[64,6144] fusion(...)",
            step + "a1/moe_shortcut/add", 0.02),
           ("%fusion.12 = f32[64,16384] fusion(...)",
            "jit(tnn_serve_decode)/lm_head/dot_general", 0.05)]
    return _obs(ops, summary={"expert_held_share": 16 / 768,
                              "experts_hit_share": 0.625,
                              "zero_pick_share": 0.3325,
                              "ffn_picks_per_token_mean": 8.01},
                token_times=[9.0, 10.5, 11.5, 12.5])


def _read(name, obs):
    how = spec.load_json("chipbench", "layer_metrics", name + ".json")
    return spec.plugin("readers", how["reader"]).read(
        obs, **how.get("args", {}))


def test_the_ten_new_metrics_read_a_recorded_scope_or_counter(recorded):
    # busy 1.00 s: both attentions 0.30, both dense feed-forwards 0.35, the
    # expert layer 0.30 of which the router 0.02
    assert _read("lcf_mla_busy_share.tok", recorded) == pytest.approx(30.0)
    assert _read("lcf_dense_ffn_busy_share.tok", recorded) \
        == pytest.approx(35.0)
    assert _read("lcf_moe_busy_share.tok", recorded) == pytest.approx(30.0)
    assert _read("lcf_route_busy_share.tok", recorded) == pytest.approx(2.0)
    assert _read("lcf_zero_pick_share.tok", recorded) == pytest.approx(33.25)
    assert _read("lcf_ffn_picks_per_token_mean.tok", recorded) \
        == pytest.approx(8.01)
    assert _read("lcf_experts_hit_share.tok", recorded) \
        == pytest.approx(62.5)
    assert _read("lcf_expert_held_share.tok", recorded) \
        == pytest.approx(100 * 16 / 768)
    # each request decoded three tokens in the slice, its 2nd to 4th, over
    # 257 to 259 and 2,273 to 2,275 positions, in each of 8 cache layers
    rows = 8 * ((257 + 258 + 259) + (2273 + 2274 + 2275))
    assert _read("lcf_mla_attn_roofline.tok", recorded) == pytest.approx(
        100 * rows * 1152 / 819e9 / 0.20)
    # three decode steps of 2 rows: 10 of 16 experts a layer read in each of
    # the four expert layers
    least = 3 * 4 * 10 * 3 * 6144 * 2048 * 2 / 819e9
    assert _read("lcf_expert_gmm_roofline.tok", recorded) == pytest.approx(
        100 * least / 0.25)
    # the generic shares read this model's scopes too
    assert _read("dense_busy_share.tok", recorded) == pytest.approx(
        100 * (0.04 + 0.02 + 0.17 + 0.03 + 0.18 + 0.05))
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
    other = _obs(ops + [("%tnn_mla_attention.1 = bf16[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0),
                        ("%tnn_expert_gmm.2 = bf16[8] custom-call()",
                         "jit(x)/sample/pallas_call", 1.0)],
                 summary={"batch_fill_mean": 1.0, "experts_hit_share": 0.5,
                          "expert_held_share": 0.25},
                 token_times=[10.5, 11.5],
                 sizes={"num_hidden_layers": 6, "hidden_size": 4096,
                        "kv_lora_rank": 256, "qk_rope_head_dim": 64,
                        "num_attention_heads": 32,
                        "moe_intermediate_size": 2048,
                        "num_experts_per_tok": 4, "held": 32})
    for name in NEW:
        assert _read(name, bare) is None, name
    for name in NEW[:2] + NEW[6:8]:     # another latent + expert family's run
        assert _read(name, other) is None, name
    assert summary_key.read({}, "zero_pick_share") is None
    assert trace_scope_share.read({}, include="moe_zero") is None
    assert trace_roofline.read({}, "^tnn_mla_attention",
                               "lcf_mla_attention") is None


def test_the_entries_are_appended_together_behind_what_was_there():
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert m["layer"] in layers
        assert m["unit"] == ("picks" if "picks" in name else "%")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        json.dumps(spec.load_json("chipbench", "layer_metrics",
                                  name + ".json"))
    # appended behind everything the benchmark had (an entry put in the
    # middle reads as a change to what was there), together and in this
    # order; NOT held to be the last, so a later PR can append behind them
    at = names.index(NEW[0])
    assert names[at - 1] == "clock_offset_ms.tok"
    assert tuple(names[at:at + len(NEW)]) == NEW
    assert [c["name"] for c in bench["configs"]].index(
        "longcat-flash-ep32-serve") == 5
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 5
    reported = {m["name"] for m in spec.metrics_of(bench, CELL, "per_layer")}
    assert set(NEW) <= reported
    # the other families' own metrics are not this cell's
    assert not {n for n in reported if n.startswith(
        ("paged_attn", "eva_", "mla_", "expert", "moe_", "win_", "full_",
         "ep8_", "sigmoid_", "idle_fetch", "mixed_"))}
    # every .tok metric that PR 37's cell joined, and not the seven that
    # tests/chipbench/test_chipbench_boundary.py holds to exactly four cells
    four = {"adopted_step_share.tok", "refused_mixed_step_share.tok",
            "step_mean_ms.tok", "host_put_p50_ms.tok",
            "host_launch_p50_ms.tok", "front_late_total_ms.tok",
            "front_late_max_ms.tok"}
    generic = {m["name"] for m in bench["per_layer"]
               if {"gpt2-large.decode", "trinity-large-ep8.decode-mixed"}
               <= set(m.get("workloads", ()))} - four
    assert len(generic) == 15 and generic <= reported
    assert not four & reported
    assert len(reported) == 15 + len(NEW)
    assert {m["name"] for m in spec.metrics_of(bench, CELL, "end_to_end")} \
        == {"out_tok_s", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
