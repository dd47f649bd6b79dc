"""The benchmark's own arithmetic, on the CPU: no chip, no network, no
topology call. (tests/chipbench/ is one of BENCHMARK.json's ``paths``.)"""
import itertools
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

from chipbench import spec, stats
from chipbench.drivers.serve_stdin import Client, Req
from chipbench.generators import closed_backlog, open_loop

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------ percentiles and gaps ----

@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6),
    ([10, 20], 95, 19.5),
    ([7], 90, 7.0),
    ([], 90, None),
])
def test_percentile_by_hand(values, q, want):
    got = stats.percentile(values, q)
    assert got == want or got == pytest.approx(want)


def test_percentile_agrees_with_numpy():
    xs = np.random.default_rng(0).exponential(size=137)
    for q in (50, 90, 95, 99):
        assert stats.percentile(list(xs), q) == pytest.approx(
            np.percentile(xs, q))


def test_iqr_share_is_the_contracts():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx(
        (q[2] - q[0]) / statistics.median(xs))


def _stream():
    """A hand-made event stream: window [100, 110). Request a was due at
    100.5, tokens at 101, 101.5, 102.5; b due at 104 with tokens at 106, 106.2;
    c due at 109, no token (gave up at 112); w was due before the window."""
    client = Client.__new__(Client)
    client.reqs, client.t_open, client.t_close = {}, 100.0, 110.0
    client.lateness = []
    for rid, due, times, measured in (
            ("a", 100.5, [101.0, 101.5, 102.5], True),
            ("b", 104.0, [106.0, 106.2], True),
            ("c", 109.0, [], True),
            ("w", 99.0, [99.5, 100.5, 111.0], False)):
        r = Req(rid, [1, 2, 3], 8, due)
        r.token_times, r.measured = times, measured
        r.streamed = [5] * len(times)
        client.reqs[rid] = r
    client.reqs["c"].end, client.reqs["c"].end_time = "cancelled", 112.0
    return {"client": client}


def test_end_to_end_metrics_on_a_hand_made_stream():
    from chipbench.end_to_end import itl_p95_ms, out_tok_s, ttft_p90_ms

    obs = _stream()
    # TTFT from the DUE instant; the request with no token counts at the
    # time it had waited when the run gave it up (112 - 109)
    assert sorted(ttft_p90_ms.samples(obs)) == pytest.approx(
        [500.0, 2000.0, 3000.0])
    assert ttft_p90_ms.value(obs) == pytest.approx(2800.0)
    # gaps of measured requests only, pooled
    assert sorted(itl_p95_ms.samples(obs)) == pytest.approx(
        [200.0, 500.0, 1000.0])
    assert itl_p95_ms.value(obs) == pytest.approx(950.0)
    # every token event inside the window counts, measured or not: 3+2+1;
    # the rate runs from the first of them (a flush of its own) to the last
    assert out_tok_s.value(obs) == pytest.approx(5 / (106.2 - 100.5))


@pytest.mark.parametrize("t_close", [151.2, 151.4])
def test_out_tok_s_does_not_depend_on_where_the_clock_cuts(t_close):
    """Bursts of 8 tokens every 0.65 s, the window opened in the middle of
    one: the same rate whether the clock cut keeps 78 bursts or 79."""
    from chipbench.end_to_end import out_tok_s

    client = Client.__new__(Client)
    client.reqs, client.t_open, client.t_close = {}, 100.0004, t_close
    for row in range(8):
        r = Req(f"r{row}", [1], 400, 0.0)
        r.token_times = [100.0 + 0.65 * k + 1e-4 * row for k in range(100)]
        client.reqs[r.id] = r
    assert out_tok_s.value({"client": client}) == pytest.approx(
        8 / 0.65, rel=1e-4)
    client.t_close = 100.0002           # nothing but part of a flush
    assert out_tok_s.value({"client": client}) is None


def test_client_gaps_reader_counts_gaps_that_end_in_the_window():
    from chipbench.readers import client_gaps

    obs = _stream()
    assert client_gaps.read(obs, percentile=100) == pytest.approx(1000.0)
    obs["client"].t_close = 102.0       # a's last gap now ends outside
    assert client_gaps.read(obs, percentile=100) == pytest.approx(500.0)


# ------------------------------------------------------------ generators ----

def _chat():
    return spec.load_json("chipbench", "traffic", "chat.json")


VOCAB = 50257


def test_open_loop_is_deterministic_in_the_seed():
    a = open_loop.plan(_chat(), 2 ** 31 + 11, 51.0, VOCAB)
    b = open_loop.plan(_chat(), 2 ** 31 + 11, 51.0, VOCAB)
    c = open_loop.plan(_chat(), 12, 51.0, VOCAB)
    assert a == b and a != c


def test_open_loop_draws_the_distributions_the_file_names():
    tr = _chat()
    tr["rate_per_s"] = 20.0             # enough requests to see shares
    reqs = open_loop.plan(tr, 2 ** 31 + 5, 51.0, VOCAB)
    n = len(reqs)
    assert n == pytest.approx(20.0 * 51.0 * 1.25, rel=0.1)
    lens = sorted(len(p) for _, p, _ in reqs)
    outs = sorted(o for _, _, o in reqs)
    pl, ol = tr["prompt_len"], tr["output_len"]
    assert pl["min"] == lens[0] and lens[-1] == pl["max"]
    assert ol["min"] == outs[0] and outs[-1] == ol["max"]
    assert statistics.median(lens) == pytest.approx(pl["median"], rel=0.08)
    assert statistics.median(outs) == pytest.approx(ol["median"], rel=0.08)
    sigma = statistics.stdev(np.log([x for x in lens
                                     if pl["min"] < x < pl["max"]]))
    assert 0.8 * pl["sigma"] < sigma <= pl["sigma"]     # clipping narrows it
    assert all(len(p) + o <= tr["max_total"] for _, p, o in reqs)
    # Poisson arrivals from a quarter window before it opens to its close
    due = [t for t, _, _ in reqs]
    assert due == sorted(due) and -12.75 <= due[0] < -12.0 and due[-1] < 51.0
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / 20.0, rel=0.1)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.15)
    # another seed: other lengths at other instants
    other = open_loop.plan(tr, 6, 51.0, VOCAB)
    assert [len(p) for _, p, _ in other[:50]] != lens[:50]
    assert [t for t, _, _ in other[:5]] != due[:5]


def test_open_loop_shares_the_prefixes_the_file_names():
    tr = _chat()
    tr["rate_per_s"] = 20.0
    reqs = open_loop.plan(tr, 3, 51.0, VOCAB)
    heads = {}
    for k in tr["prefixes"]:            # a head that several prompts start with
        seen = {}
        for _, p, _ in reqs:
            seen.setdefault(tuple(p[:k["len"]]), []).append(p)
        heads.update({h: k["len"] for h, ps in seen.items()
                      if len(ps) > 1 and len(h) == k["len"]})
    long_heads = {h for h, n in heads.items() if n == 256}
    heads = {h: n for h, n in heads.items()
             if n == 256 or not any(lh[:128] == h for lh in long_heads)}
    assert sorted(heads.values()) == [128] * 4 + [256] * 4
    shared = [next((n for h, n in heads.items() if tuple(p[:n]) == h), 0)
              for _, p, _ in reqs]
    can = [len(p) >= 256 + tr["min_own_tail"] for _, p, _ in reqs]
    share = sum(1 for n, c in zip(shared, can) if n and c) / sum(can)
    assert share == pytest.approx(tr["shared_share"], abs=0.05)
    assert all(len(p) >= n + tr["min_own_tail"]
               for (_, p, _), n in zip(reqs, shared) if n)


def _once(tr, seed, vocab, scale=1.0):
    """The file's list gone through once: [(prompt, max_new)]."""
    return list(itertools.islice(
        closed_backlog.requests(tr, seed, vocab, scale), len(tr["requests"])))


def test_closed_backlog_plan():
    """Two seeds send the same sequence of (prompt length, output length)
    with other ids: the order and the pairing are the file's, not the
    seed's."""
    tr = spec.load_json("chipbench", "traffic", "decode.json")
    a = _once(tr, 2 ** 31 + 5, VOCAB)
    b = _once(tr, 7, VOCAB)
    assert a == _once(tr, 2 ** 31 + 5, VOCAB) and a != b
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b] \
        == [tuple(r) for r in tr["requests"]]
    assert all(p != q for (p, _), (q, _) in zip(a, b))
    assert all(0 <= t < VOCAB for p, _ in a[:4] for t in p)
    assert max(t for p, _ in a for t in p) > VOCAB // 2
    small = _once(tr, 7, 1000)
    assert max(t for p, _ in small for t in p) < 1000
    # a run that needs more than the list holds starts it again, new ids
    more = list(itertools.islice(
        closed_backlog.requests(tr, 7, VOCAB), 2 * len(a)))
    assert more[:len(a)] == b
    assert [(len(p), o) for p, o in more[len(a):]] == \
        [(len(p), o) for p, o in b] and more[len(a)][0] != b[0][0]
    # the rehearsal's scale shrinks every length alike
    tiny = _once(tr, 7, VOCAB, 0.125)
    assert [(len(p), o) for p, o in tiny] == [
        (max(1, round(p * 0.125)), max(1, round(o * 0.125)))
        for p, o in tr["requests"]]


def test_closed_backlog_file_holds_the_same_lengths_in_every_wave():
    tr = spec.load_json("chipbench", "traffic", "decode.json")
    w, reqs = tr["wave"], tr["requests"]
    assert tr["outstanding"] == 2 * w and len(reqs) % w == 0
    waves = [reqs[i:i + w] for i in range(0, len(reqs), w)]
    # every wave holds the same lengths, spread evenly over the range, in
    # another order and pairing: the rows of a window hold the same contexts
    even = lambda lo, hi: [round(lo + (hi - lo) * (i + 0.5) / w)     # noqa
                           for i in range(w)]
    assert all(sorted(p for p, _ in x) == even(64, 640) for x in waves)
    assert all(sorted(o for _, o in x) == even(128, 384) for x in waves)
    assert len({tuple(map(tuple, x)) for x in waves}) == len(waves) >= 4
    assert sum(p for p, _ in waves[0]) / w == (64 + 640) / 2
    assert max(p + o for p, o in reqs) <= 1024


# --------------------------------------------------------------- opcount ----

LARGE = dict(n_layer=36, n_embd=1280, n_head=20, vocab_size=50257,
             n_positions=1024)
MEDIUM = dict(n_layer=24, n_embd=1024, n_head=16, vocab_size=50257,
              n_positions=1024)


def test_opcount_paged_attention_by_hand():
    from chipbench.opcount import paged_attention as pa

    # K and V of one position: 2 x 36 layers x 1280 values x 2 bytes
    assert pa.kv_bytes_per_context_token(LARGE) == 184320
    w = pa.decode_work([500, 1000], LARGE)
    assert w["bytes"] == 1500 * 184320
    assert w["flops"] == 4 * 1500 * 1280 * 36


def test_opcount_flash_and_model_flops_by_hand():
    from chipbench.opcount import flash_attention as fa
    from chipbench.opcount import lm_train

    # one causal matmul: 2 * B*H*S*S*Dh / 2 = 8*16*1024*1024*64 = 2**33
    assert fa.step_flops(8, 1024, MEDIUM) == 24 * 6 * 2 ** 33
    # matmul parameters of GPT-2 medium: 12 d^2 per layer + the tied table
    want = 24 * 12 * 1024 ** 2 + 50257 * 1024
    assert lm_train.matmul_params(MEDIUM) == want
    assert lm_train.flops_per_token(MEDIUM, 1024) == \
        6 * want + 6 * 24 * 1024 * 1024


def test_peaks_table_refuses_an_unknown_chip():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


# ---------------------------------------------------------------- xplane ----

def test_xplane_reduction_of_the_recorded_trace():
    """chipbench/reduce/sample_v5e.xplane.pb: three calls of a small jitted
    function (the paged kernel at toy shapes, then a matmul + tanh) recorded
    on a TPU v5 lite in PR 23, 10 ms of host sleep between them."""
    from chipbench.readers import trace_idle, trace_roofline
    from chipbench.reduce import xplane

    tr = xplane.reduce_file(os.path.join(
        ROOT, "chipbench", "reduce", "sample_v5e.xplane.pb"))
    assert tr["chips"] == 1
    assert tr["busy_s"] == pytest.approx(23.3e-6, rel=0.02)
    assert tr["window_s"] == pytest.approx(22.14e-3, rel=0.01)
    assert tr["top_ops"][0][0] == "custom-call tiny"
    assert tr["top_ops"][0][1] == pytest.approx(20.0e-6, rel=0.02)
    assert tr["top_gaps"][0][0] == "$time sleep"
    obs = {"trace": tr}
    # a kernel is found by its instruction's name, not by the text after it
    assert 100 * trace_roofline.kernel_seconds(tr, "^tiny") / tr["busy_s"] \
        == pytest.approx(85.8, abs=1.0)
    assert trace_roofline.kernel_seconds(tr, "tpu_custom_call") == 0
    assert trace_roofline.kernel_seconds(tr, "no such kernel") == 0
    assert trace_idle.read(obs) == pytest.approx(99.89, abs=0.05)


def test_xplane_helpers():
    from chipbench.reduce import xplane

    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane.group_of(
        "%copy.188 = bf16[36,704,20,16,64]{4,2,3,1,0:T(8,128)(2,1)} "
        "copy(bf16[36,704,20,16,64]{1,4,3,2,0} %p)") == \
        "copy bf16[36,704,20,16,64]"
    assert xplane.group_of(
        "%fused_computation.12 = f32[8,64]{1,0} fusion(f32[8] %a), "
        "kind=kLoop") == "fusion fused_computation"


# ------------------------------------------------------------- reference ----

def test_reference_matches_the_programs_forward_in_float32():
    import jax

    from chipbench.reference import gpt2 as ref
    from tnn_tpu import models
    from tnn_tpu.core.dtypes import DTypePolicy

    sz = dict(n_layer=2, n_embd=128, n_head=2, vocab_size=50257,
              n_positions=1024)
    params = ref.make_params(sz, 2 ** 31 + 3)
    f32 = DTypePolicy(io="float32", param="float32", compute="float32")
    model = models.create("gpt2_tiny", policy=f32)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                              (1, 8))["params"]))
    ids = np.random.default_rng(0).integers(0, 50257, 96).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.apply({"params": params, "state": {}}, ids[None])
    got = ref.Forward(params, sz, 128).rows(ids, np.arange(96))
    assert got.std() > 0.15         # 0.02 x sqrt(n_embd)
    np.testing.assert_allclose(got, np.asarray(want[0]), atol=2e-4)
    # the int8 control is a different function, visibly
    low = ref.Forward(params, sz, 128, quant="int8").rows(ids, np.arange(96))
    assert np.abs(low - got).max() > 20 * np.abs(
        got - np.asarray(want[0])).max()


def test_reference_adamw_by_hand():
    import jax.numpy as jnp

    from chipbench.reference import adamw

    assert adamw.lr_scale(0, 100, 2000) == 0.0
    assert adamw.lr_scale(50, 100, 2000) == 0.5
    assert adamw.lr_scale(1050, 100, 2000) == pytest.approx(0.5)
    g = adamw.clip({"a": jnp.array([3.0, 4.0])}, 1.0)
    assert np.allclose(g["a"], [0.6, 0.8], atol=1e-5)
    p = {"a": jnp.array([1.0, -2.0])}
    new, st = adamw.update(p, {"a": jnp.array([0.5, -0.25])}, adamw.init(p),
                           lr=0.1, weight_decay=0.01)
    # first step: m/(1-b1) = g, sqrt(v/(1-b2)) = |g| -> update = sign(g)
    assert np.allclose(new["a"], [1.0 - 0.1 - 0.001, -2.0 + 0.1 + 0.002],
                       atol=1e-6)
    assert st["t"] == 1


# ---------------------------------------------------------------- schema ----

def test_benchmark_json_names_units_and_files():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in b["paths"])
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        cfg = spec.load_json(c["file"])
        assert cfg["reduced"] == c["reduced"]       # empty or not:
        spec.check_cut(c, cfg, spec.plugin("reference", cfg["reference"]))
        spec.plugin("drivers", cfg["driver"])
        spec.plugin("drivers", cfg["driver"] + "_check")
        names.append(c["name"])
    cells = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and w["config"] in names
        tr = spec.load_json("chipbench", "traffic", w["traffic"] + ".json")
        spec.plugin("generators", tr["generator"])
        cells[w["name"]] = w
        names.append(w["name"])
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        spec.plugin("end_to_end", m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
        names.append(m["name"])
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        how = spec.load_json("chipbench", "layer_metrics",
                             m["name"] + ".json")
        spec.plugin("readers", how["reader"])
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"])
               for m in b["end_to_end"] + b["per_layer"])
    assert all(m["better"] in ("lower", "higher")
               for m in b["end_to_end"] + b["per_layer"])
    for name in cells:          # every cell reports set-up, one more, a layer
        assert sum(1 for ws in e2e.values() if name in ws) >= 2
        assert spec.metrics_of(b, name, "per_layer")
    # the full check of 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_files_under_paths_have_plain_names():
    b = spec.benchmark()
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in b["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), ROOT))


# ---------------------------------------------------------- no chip, no run ----

def test_run_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2-large.decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert "{" not in r.stdout          # no result line, no metric
    assert "needs 1 TPU chip" in r.stderr
