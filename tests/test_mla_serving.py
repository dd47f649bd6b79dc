"""Mistral Small 4's block on the normal serving path (PR 32): latent (MLA)
attention over latent pages read once for keys and values, and one chip's
share of a dropless expert layer. Tiny sizes on the CPU (2 layers, 64 wide, 4
heads, latent 32 + 16, 8 of 16 experts of width 32 held, 4 a token, one
shared), seeded weights, logits held against ``chipbench/reference/
mistral4.py``: the same module the benchmark compares with, which imports
nothing of the program and writes the EXPANDED form of the attention."""
import contextlib
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mistral4 as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.nn.moe import ExpertShare, collect_counts
from tnn_tpu.ops.pallas import expert_gmm as gmm
from tnn_tpu.ops.pallas import mla_attention as mla
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving.kv_pool import PagedKVPool

ROPE = dict(rope_theta=10000.0, factor=4.0,
            original_max_position_embeddings=32, beta_fast=32, beta_slow=1,
            mscale=1, mscale_all_dim=1, llama_4_scaling_beta=0.1)
CFG = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
           q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=16, v_head_dim=16, moe_intermediate_size=32,
           num_experts_per_tok=4, n_shared_experts=1, n_routed_experts=8,
           published={"n_routed_experts": 16}, vocab_size=256,
           max_position_embeddings=256, served_positions=256,
           rms_norm_eps=1e-6, rope_parameters=dict(ROPE, rope_type="yarn"))
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The program in float32 (absorbed form, pages, sorted experts) against the
# float32 reference at precision "highest" (expanded form, no cache, experts
# one at a time): what is left is the order of sums. Logits of a model 64
# wide are O(1); 2e-4 is a hundred float32 steps of them. The SAME program in
# bfloat16 misses it by two orders of magnitude (asserted below).
TOL = 2e-4


@pytest.fixture(scope="module")
def sz():
    return ref.sizes_of(CFG)


@pytest.fixture(scope="module")
def weights(sz):
    p = ref.make_params(sz, 32)
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(sz):
    m = models.create("mistral_small4_tiny", policy=F32)
    ref.check_program(m, sz, "mistral_small4_tiny")
    return m


@pytest.fixture(scope="module")
def forward(weights, sz):
    return ref.Forward(weights[0], sz, 128)


def engine(model, params, **kw):
    kw = dict(dict(num_blocks=64, block_size=8, max_batch_size=4,
                   chunk_size=16, prefix_cache=False, max_seq_len=192), **kw)
    return InferenceEngine(model, params, **kw)


# -- (1) logits through the latent pool against the expanded reference --------

def _paged_logits(model, params, ids, chunk, bs=8):
    """Chunked prefill (ragged: the last chunk is short) and then decode,
    one sequence in row 1 of a batch of 2 (row 0 is padding), straight
    through ``apply_paged`` / ``apply_decode_paged``: logits at every
    position."""
    n_prompt = 37
    pool = PagedKVPool(model.num_layers, 1, model.latent_row, 32, bs,
                       dtype=jnp.float32, latent=True)
    assert pool.page_shape == (2, 32, 1, bs, 128)
    assert pool.pages_v.size == 2 * 8 * 128         # no value pool
    table = np.zeros((2, 16), np.int32)
    table[1] = np.arange(1, 17)
    pk, pv = pool.pages_k, pool.pages_v
    out = []
    at = 0
    while at < n_prompt:
        n = min(chunk, n_prompt - at)
        toks = np.zeros((2, chunk), np.int32)
        toks[1, :n] = ids[at:at + n]
        lg, pk, pv = model.apply_paged(
            params, jnp.asarray(toks), pk, pv, jnp.asarray(table),
            jnp.asarray([0, at]), jnp.asarray([0, n]))
        out.append(np.asarray(lg[1, :n]))
        at += n
    for t in range(n_prompt, len(ids)):
        lg, pk, pv = model.apply_decode_paged(
            params, jnp.asarray([0, ids[t]]), pk, pv, jnp.asarray(table),
            jnp.asarray([0, t]))
        out.append(np.asarray(lg[1:2]))
    return np.concatenate(out)


def test_chunked_prefill_then_decode_match_the_expanded_reference(
        model, weights, forward):
    ids = np.random.default_rng(5).integers(0, 256, 90).astype(np.int32)
    want = forward.rows(list(ids), np.arange(90))
    got = _paged_logits(model, weights[1], ids, chunk=16)
    assert np.abs(got - want).max() < TOL
    # positions 32 and 64 change the query's scale and YaRN bends the
    # frequencies: the plain forward (expanded, no pool) agrees too
    full, _ = model.apply({"params": weights[1], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full[0]) - want).max() < TOL
    # a bf16 program does not pass this tolerance: it is a float32 one
    low = models.create("mistral_small4_tiny", policy=DTypePolicy(
        io="bfloat16", param="bfloat16", compute="bfloat16"))
    full16, _ = low.apply({"params": weights[0], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full16[0], np.float32) - want).max() > 50 * TOL


def test_the_engine_serves_the_reference_tokens(model, weights, forward):
    p = np.random.default_rng(0).integers(0, 256, 37).astype(np.int32)
    eng = engine(model, weights[1])
    rid = eng.submit(p, 80)
    short = eng.submit(p[:9], 20)
    out = eng.run_until_complete()
    eng.check_invariants()
    lg = forward.rows(list(p) + out[rid], np.arange(36, 116))
    assert (lg.argmax(-1) == np.asarray(out[rid])).all()
    assert len(out[short]) == 20 and eng.pool.num_allocated == 0
    assert eng.stats()["kv_bytes_per_token"] == 2 * 128 * 4   # one array


# -- (2) the router -------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_top4_agree_with_the_reference(sz, weights, seed):
    """The program's choice of experts against the reference's, counted over
    every token and layer: zero disagreements on these seeds."""
    share = ExpertShare(16, range(8), 4, 32, shared=1, policy=F32)
    g = jax.random.normal(jax.random.PRNGKey(seed), (256, 64), jnp.float32)
    wrong = 0
    for i in range(2):
        p = weights[1][f"h{i}"]["moe"]
        ids, w = share.route(p, g)
        want = np.asarray(ref.route(p, g, sz))
        got = np.zeros_like(want)
        np.put_along_axis(got, np.asarray(ids), np.asarray(w), axis=1)
        wrong += int(((got > 0) != (want > 0)).any(axis=1).sum())
        assert np.abs(got - want).max() < 1e-6
    assert wrong == 0


# -- (3) the two kernels, interpreted, against plain jax.numpy ----------------

@pytest.mark.kernel
@pytest.mark.parametrize("qw,rows,dtype", [
    (1, 512, jnp.float32), (16, 512, jnp.float32), (16, 16, jnp.float32),
    (8, 16, jnp.bfloat16)])
def test_mla_kernel_matches_the_plain_path(monkeypatch, qw, rows, dtype):
    """Decode and chunk forms; ``rows`` 16 cuts a chunk's queries into tiles
    of 4 tokens, each a batch row of its own (``_tile_rows``); two pages a
    grid step, so groups end inside and past a row's length."""
    monkeypatch.setattr(mla, "QUERY_ROWS", rows)
    monkeypatch.setattr(mla, "GROUP_POSITIONS", 16)
    jax.clear_caches()
    rng = np.random.default_rng(qw)
    b, h, bs, nb, row, dv = 3, 4, 8, 6, 128, 32
    pages = jnp.asarray(rng.normal(size=(2, 24, 1, bs, row)), dtype)
    q = jnp.asarray(rng.normal(size=(b, qw, h, row)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, 19)).reshape(b, nb),
                         jnp.int32)
    q_lens = jnp.asarray([qw, max(qw - 3, 1), 0], jnp.int32)
    kv_lens = jnp.asarray([41, 17, 0], jnp.int32)
    kw = dict(value_dim=dv, q_lens=q_lens, layer=1, scale=0.2)
    got = mla.mla_attention(q, pages, tables, kv_lens, backend="pallas",
                            interpret=True, **kw)
    want = mla.mla_attention(q, pages, tables, kv_lens, backend="xla", **kw)
    assert got.shape == (b, qw, h, dv)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol
    assert not np.asarray(got[2], np.float32).any()     # a dead row: zeros
    jax.clear_caches()


@pytest.mark.kernel
@pytest.mark.parametrize("tile", [16, 128])
def test_expert_gmm_kernel_matches_the_plain_path(monkeypatch, tile):
    monkeypatch.setattr(gmm, "F_BLOCK", 128)        # two blocks of F = 256
    rng = np.random.default_rng(tile)
    e, f, d = 4, 256, 128
    gate, up, down = (jnp.asarray(rng.normal(size=(e, f, d)) / 12,
                                  jnp.float32) for _ in range(3))
    # experts 0, 2, 2, 3 hold the live tiles; two tiles are dead
    tile_expert = jnp.asarray([0, 2, 2, 3, 3, 3], jnp.int32)
    x = jnp.asarray(rng.normal(size=(6 * tile, d)), jnp.float32)
    args = (x, gate, up, down, tile_expert, jnp.int32(4))
    got = gmm.expert_gmm(*args, tile=tile, backend="pallas", interpret=True)
    want = gmm.expert_gmm(*args, tile=tile, backend="xla")
    live = 4 * tile
    assert np.abs(np.asarray(got[:live]) - np.asarray(want[:live])).max() \
        < 1e-4
    by_hand = (jax.nn.silu(x[tile:2 * tile] @ gate[2].T)
               * (x[tile:2 * tile] @ up[2].T)) @ down[2]
    assert np.abs(np.asarray(got[tile:2 * tile]) - np.asarray(by_hand)).max() \
        < 1e-4


# -- (4) dropless, and the shares add up ------------------------------------------

def test_every_token_to_one_expert_drops_nothing(sz, weights):
    """A router that sends EVERY token to expert 3 first (and 300 tokens at
    once, nineteen tiles of one expert): nothing is dropped, the layer is
    still the reference's sum."""
    share = ExpertShare(16, range(8), 4, 32, shared=1, policy=F32)
    p = dict(weights[1]["h0"]["moe"])
    p["router"] = p["router"].at[:, 3].set(0.0).at[0, 3].set(50.0)
    g = jax.random.normal(jax.random.PRNGKey(4), (300, 64), jnp.float32)
    g = g.at[:, 0].set(jnp.abs(g[:, 0]) + 1.0)
    y, counts = share.routed(p, g)
    assert int(counts[3]) == 300
    want = ref.experts(p, g, sz)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5
    # a dead token takes no expert and counts nowhere
    live = jnp.arange(300) < 200
    y2, counts2 = share.routed(p, g, live)
    assert int(counts2[3]) == 200 and int(counts2.sum()) * 3 // 2 \
        >= int(counts.sum()) - 150
    assert not np.asarray(y2[200:]).any()
    assert np.abs(np.asarray(y2[:200]) - np.asarray(want[:200])).max() < 1e-5


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Sixteen experts on four chips of four: the routed parts of the four
    shares (experts 0-3, 4-7, 8-11, 12-15, each by the PROGRAM's layer told
    which it holds) plus the shared expert once are the reference's UNCUT
    layer. What a share leaves out is exactly what the others add."""
    whole = ref.sizes_of(dict(CFG, n_routed_experts=16, published={}))
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                               ref.make_params(whole, 7))["h1"]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(9), (96, 64), jnp.float32)
    want = ref.experts(p, g, whole) + ref.shared(p, g)
    total = jnp.zeros_like(g)
    counted = 0
    for k in range(4):
        held = range(4 * k, 4 * k + 4)
        share = ExpertShare(16, held, 4, 32, shared=1, policy=F32)
        mine = dict(p, **{n: p[n][4 * k:4 * k + 4]
                          for n in ("gate", "up", "down")})
        y, counts = share.routed(mine, g)
        total += y
        counted += int(counts.sum())
        # and the reference given the same share says the same
        part = ref.experts(mine, g, whole, which=held)
        assert np.abs(np.asarray(y) - np.asarray(part)).max() < 1e-5
    total += ExpertShare(16, range(4), 4, 32, shared=1, policy=F32) \
        .shared_out(p, g)
    assert counted == 96 * 4                # every assignment, once
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


def test_step_programs_return_the_counters_with_the_tokens(model, weights):
    """One fetch a step: the (layers, held) counts ride beside the tokens,
    and the window's summary folds them."""
    eng = engine(model, weights[1], overlap=True)
    p = np.random.default_rng(2).integers(0, 256, 21).astype(np.int32)
    for n in (21, 10, 16):
        eng.submit(p[:n], 12)
    eng.run_until_complete()
    s = eng.metrics.summary()
    layers, top_k = 2, 4
    tokens = s["decode_tokens"] + s["prefill_tokens"]
    assert eng.metrics.expert_assignments == tokens * top_k * layers
    assert 0.2 < s["expert_held_share"] < 0.8
    assert 0 < s["experts_hit_share"] <= 1
    assert s["expert_load_max_over_mean"] >= 1
    assert s["host_gap_ms_p50"] >= 0        # and no second sync was added:
    with collect_counts() as counts:        # the program's own extra output
        model.apply_paged(
            weights[1], jnp.zeros((2, 4), jnp.int32), eng.pool.pages_k,
            eng.pool.pages_v, jnp.zeros((2, eng.blocks_per_seq), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([4, 0]))
    assert len(counts) == layers and counts[0].shape == (8,)
    assert int(counts[0].sum()) <= 4 * top_k    # the dead row took none


# -- (5) the refusals -----------------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(spec="ngram"), "speculative decoding"),
    (dict(tp=2), "tensor parallelism"),
    (dict(sp=2), "sequence parallelism"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix sharing"),
    (dict(kv_dtype="int8"), "int8 pages")])
def test_the_engine_refuses_what_assumes_kv_blocks_of_heads(
        model, weights, kw, what):
    with pytest.raises(ValueError, match="one latent row a token") as e:
        engine(model, weights[1], **kw)
    assert what in str(e.value) and str(e.value).count(".") <= 1


def test_one_refusal_function_for_both_kinds_of_state(model):
    msg = refuse_windowed(model, host_tier_bytes=1 << 20)
    assert "host KV tier" in msg and "latent row" in msg
    assert refuse_windowed(model) is None
    eva = refuse_windowed(models.create("evabyte_tiny"), prefix_cache=True)
    assert "exact window of 32" in eva and "latent" not in eva


@pytest.mark.parametrize("flags,what", [
    ([], "prefix sharing"),
    (["--no-prefix-cache", "--spec", "ngram"], "speculative decoding"),
    (["--no-prefix-cache", "--tp", "2"], "tensor parallelism"),
    (["--no-prefix-cache", "--sp", "2"], "sequence parallelism"),
    (["--no-prefix-cache", "--kv-dtype", "int8"], "int8 pages")])
def test_tnn_serve_says_so_at_start_up_before_any_weights(flags, what):
    from tnn_tpu.cli import serve

    err = io.StringIO()
    with contextlib.redirect_stderr(err), pytest.raises(SystemExit) as e:
        serve.main(["--model", "mistral_small4_tiny", *flags])
    assert e.value.code == 2 and what in err.getvalue()
    assert "random-weight" not in err.getvalue()


def test_the_pool_refuses_a_latent_row_it_cannot_page():
    for kw in (dict(num_kv_heads=2), dict(head_dim=320), dict(sp=2),
               dict(kv_dtype="int8"), dict(window=32, chunk=4)):
        args = dict(dict(num_layers=2, num_kv_heads=1, head_dim=384,
                         num_blocks=8, block_size=8, latent=True), **kw)
        with pytest.raises(ValueError):
            PagedKVPool(**args)


# -- (6) the engine's feature suites, the third family ------------------------------

def _greedy(model, params, prompt, max_new, max_len):
    from tnn_tpu.models.gpt2 import generate

    return np.asarray(generate(model, params, prompt[None], max_new,
                               max_len=max_len))[0].tolist()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 128, p).astype(np.int32) for p in (5, 9, 16, 7)]


def test_staggered_parity_with_the_offline_expanded_form(mistral_lm, prompts):
    """Ragged admission through the absorbed form over pages equals
    ``generate``, which runs the expanded form over an assembled cache."""
    model, params = mistral_lm
    eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                          max_batch_size=4, max_seq_len=32,
                          prefix_cache=False)
    rids = [eng.submit(prompts[0], 10)]
    eng.step(); eng.step()
    rids += [eng.submit(p, 10) for p in prompts[1:]]
    out = eng.run_until_complete()
    assert {k[0] for k in eng._jit} == {"pdecode", "mixed"}
    for rid, p in zip(rids, prompts):
        assert out[rid] == _greedy(model, params, p, 10, eng.assembly_len)


def test_mixed_sampling_and_stop_token(mistral_lm):
    model, params = mistral_lm
    eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                          max_batch_size=4, max_seq_len=32, seed=3,
                          prefix_cache=False)
    p = np.arange(6, dtype=np.int32)
    ref_toks = _greedy(model, params, p, 10, eng.assembly_len)
    g = eng.submit(p, 10)
    s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
    stop = eng.submit(p, 10, stop_token=ref_toks[3])
    out = eng.run_until_complete()
    assert out[g] == ref_toks
    assert len(out[s]) == 8 and all(0 <= t < 128 for t in out[s])
    assert out[stop] == ref_toks[:ref_toks.index(ref_toks[3]) + 1]
    assert eng.pool.num_allocated == 0


@pytest.mark.parametrize("knob", ["overlap", "overlap_deep", "trace"])
def test_overlap_and_tracing_change_no_token(mistral_lm, prompts, knob,
                                             monkeypatch):
    """``overlap_deep``: a step more queued with every adoption, on a pool
    small enough to preempt."""
    model, params = mistral_lm
    if knob == "overlap_deep":
        from tnn_tpu.serving import engine as engine_lib

        monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", 1)
        monkeypatch.setattr(engine_lib, "SPECULATE_AHEAD_S", 3600.0)

    def run(**kw):
        eng = InferenceEngine(model, params, num_blocks=9, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              prefix_cache=False, **kw)
        rids = [eng.submit(p, 10) for p in prompts]
        out = eng.run_until_complete()
        return [out[r] for r in rids], eng

    if knob.startswith("overlap"):
        off, _ = run(overlap=False)
        on, eng = run(overlap=True)
        assert eng.metrics.preemptions > 0 and len(eng.metrics.host_gap_s) > 0
    else:
        off, _ = run()
        on, eng = run(trace=True)
        names = [ev.name for ev in eng.profiler.events]
        spans = [n for n in names if n.startswith("serve.dispatch")]
        assert spans and all("experts_held=8" in n and "latent_pages=" in n
                             for n in spans)
    assert on == off
