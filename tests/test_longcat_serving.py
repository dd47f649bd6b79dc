"""LongCat-Flash's block on the normal serving path (PR 41): TWO latent
attentions, each over cache rows of its own, two dense feed-forwards and ONE
shortcut expert layer across them; a softmax router over experts and
zero-compute identity experts that selects with a bias, weighs without it
and does not renormalise. Tiny sizes on the CPU (2 blocks = 4 cache layers, 64
wide, 4 heads, latent 32 + 16, a router of 24 = 16 experts + 8 zero-compute,
6 a token, 8 experts of width 32 held), seeded weights, logits held against
``chipbench/reference/longcat_flash.py``: the same module the benchmark
compares with, which imports nothing of the program and writes the EXPANDED
form of the attention."""
import contextlib
import io
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import longcat_flash as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.nn.attention import LatentAttention, yarn_inv_freq
from tnn_tpu.nn import moe as moe_lib
from tnn_tpu.nn.moe import ExpertShare, collect_counts
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving.kv_pool import PagedKVPool

CFG = spec.load_json("chipbench", "configs",
                     "longcat-flash-ep32-serve.json")["rehearsal"]
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The program in float32 (absorbed form, pages, sorted experts) against the
# float32 reference at precision "highest" (expanded form, no cache, experts
# one at a time): what is left is the order of sums. Logits of a model 64
# wide are O(1); 2e-4 is a hundred float32 steps of them.
TOL = 2e-4
SHARE = dict(num_experts=16, zero_experts=8, top_k=6, hidden=32,
             score="softmax_raw", route_scale=6.0)


@pytest.fixture(scope="module")
def sz():
    return ref.sizes_of(CFG)


@pytest.fixture(scope="module")
def weights(sz):
    p = ref.make_params(sz, 41)
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(sz):
    m = models.create("longcat_flash_tiny")         # float32 by default
    ref.check_program(m, sz, "longcat_flash_tiny")
    return m


@pytest.fixture(scope="module")
def forward(weights, sz):
    return ref.Forward(weights[0], sz, 128)


def engine(model, params, **kw):
    kw = dict(dict(num_blocks=64, block_size=8, max_batch_size=4,
                   chunk_size=16, prefix_cache=False, max_seq_len=192), **kw)
    return InferenceEngine(model, params, **kw)


# -- (1) logits through the latent pool against the expanded reference --------

def _paged_logits(model, params, ids, n_prompt, chunk, bs=8, second=None):
    """Chunked prefill (ragged: the last chunk is short) and then decode,
    one sequence in row 1 of a batch of 2 (row 0 is padding), straight
    through ``apply_paged`` / ``apply_decode_paged``: (logits at every
    position, the pool's pages at the end). ``second``: the cache layer every
    block's SECOND attention is handed (None: its own)."""
    pool = PagedKVPool(model.cache_layers, 1, model.latent_row, 32, bs,
                       dtype=jnp.float32, latent=True)
    assert pool.page_shape == (4, 32, 1, bs, 128)   # two layers a block
    table = np.zeros((2, 16), np.int32)
    table[1] = np.arange(1, 17)
    pk, pv = pool.pages_k, pool.pages_v
    if second is not None:
        real = model._paged_layers

        def same_layer(pages_k, block_tables):
            return [dict(w, layer=(w["layer"][0], w["layer"][second]))
                    for w in real(pages_k, block_tables)]

        model = models.create("longcat_flash_tiny")
        model._paged_layers = same_layer
    out, at = [], 0
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
    return np.concatenate(out), np.asarray(pk)


@pytest.mark.parametrize("n_prompt,chunk", [
    (37, 16), (7, 8), (8, 8), (9, 8), (15, 4), (16, 16), (17, 16), (33, 16)])
def test_chunked_prefill_then_decode_match_the_reference(
        model, weights, forward, n_prompt, chunk):
    """Prompt lengths around a page's edge (pages of 8): every logit of the
    prefill and of the decode steps behind it."""
    ids = np.random.default_rng(n_prompt).integers(0, 256, n_prompt + 20) \
        .astype(np.int32)
    want = forward.rows(list(ids), np.arange(len(ids)))
    got, _ = _paged_logits(model, weights[1], ids, n_prompt, chunk)
    assert np.abs(got - want).max() < TOL


def test_the_plain_forward_the_cached_one_and_bf16(model, weights, forward):
    ids = np.random.default_rng(5).integers(0, 256, 90).astype(np.int32)
    want = forward.rows(list(ids), np.arange(90))
    full, _ = model.apply({"params": weights[1], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full[0]) - want).max() < TOL
    # the offline cached decode: each block keeps a cache an attention
    caches = model.init_cache(1, 96)
    assert len(caches) == 2 and len(caches[0]) == 2
    lg, caches = model.apply_cached(weights[1], jnp.asarray(ids[:60])[None],
                                    caches, 0)
    assert np.abs(np.asarray(lg[0]) - want[:60]).max() < TOL
    lg, _ = model.apply_cached(weights[1], jnp.asarray(ids[60:61])[None],
                               caches, 60)
    assert np.abs(np.asarray(lg[0, 0]) - want[60]).max() < TOL
    # a bf16 program does not pass this tolerance: it is a float32 one
    low = models.create("longcat_flash_tiny", policy=DTypePolicy(
        io="bfloat16", param="bfloat16", compute="bfloat16"))
    full16, _ = low.apply({"params": weights[0], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full16[0], np.float32) - want).max() > 50 * TOL


@pytest.mark.parametrize("without", ref.WITHOUT)
def test_the_comparison_sees_each_equation(weights, sz, forward, without):
    """The shortcut term, the identity experts, the missing renormalisation,
    either rank scale, the second attention's own cache rows: each one left
    out of (or swapped in) the reference moves the logits a thousand
    tolerances."""
    ids = np.random.default_rng(6).integers(0, 256, 64).astype(np.int32)
    want = forward.rows(list(ids), np.arange(64))
    got = ref.Forward(weights[0], sz, 128, without=(without,)).rows(
        list(ids), np.arange(64))
    assert np.abs(got - want).max() > 1000 * TOL


def test_a_block_whose_second_attention_shares_the_first_ones_layer(
        model, weights, forward):
    """The program handed ONE cache layer for both attentions of a block is
    what the reference computes with the second attention reading... neither:
    the second write lands on the first's rows, and the logits leave the
    reference's by a thousand tolerances. Handed two, the two layers of a
    block hold different rows."""
    ids = np.random.default_rng(7).integers(0, 256, 40).astype(np.int32)
    want = forward.rows(list(ids), np.arange(40))
    got, pages = _paged_logits(model, weights[1], ids, 29, 16)
    assert np.abs(got - want).max() < TOL
    for blk in range(2):
        a, b = pages[2 * blk, 1:6], pages[2 * blk + 1, 1:6]
        assert np.abs(a).max() > 0 and np.abs(b).max() > 0
        assert np.abs(a - b).max() > 0.1        # 40 positions: pages 1..5
    assert not pages[:, 6:].any()               # nothing past the sequence
    bad, pages = _paged_logits(model, weights[1], ids, 29, 16, second=0)
    assert np.abs(bad - want).max() > 1000 * TOL
    assert not pages[1].any() and not pages[3].any()


def test_the_engine_serves_the_reference_tokens(model, weights, forward):
    p = np.random.default_rng(0).integers(0, 256, 37).astype(np.int32)
    eng = engine(model, weights[1])
    assert eng.pool.num_layers == 4 == model.cache_layers
    rid = eng.submit(p, 80)
    short = eng.submit(p[:9], 20)
    out = eng.run_until_complete()
    eng.check_invariants()
    lg = forward.rows(list(p) + out[rid], np.arange(36, 116))
    assert (lg.argmax(-1) == np.asarray(out[rid])).all()
    assert len(out[short]) == 20 and eng.pool.num_allocated == 0
    # one array, a row of 128 lanes a token in each of FOUR cache layers
    assert eng.stats()["kv_bytes_per_token"] == 4 * 128 * 4


# -- (2) the router, by hand -----------------------------------------------------

def _by_hand(p, g, sz):
    """(ids (T, k), weights (T, k)) in numpy, from the definition."""
    logits = np.asarray(g, np.float64) @ np.asarray(p["router"], np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    ids = np.argsort(-(probs + np.asarray(p["expert_bias"], np.float64)),
                     axis=-1, kind="stable")[:, :sz["moe_topk"]]
    return ids, sz["routed_scaling_factor"] * np.take_along_axis(
        probs, ids, -1)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_top6_agree_with_the_definition_and_the_reference(sz, weights, seed):
    share = ExpertShare(held=range(8), policy=F32, **SHARE)
    g = jax.random.normal(jax.random.PRNGKey(seed), (256, 64), jnp.float32)
    for i in range(2):
        p = weights[1][f"h{i}"]["moe"]
        ids, w = share.route(p, g)
        want_ids, want_w = _by_hand(p, g, sz)
        assert (np.sort(np.asarray(ids), -1) == np.sort(want_ids, -1)).all()
        assert np.abs(np.sort(np.asarray(w), -1)
                      - np.sort(want_w, -1)).max() < 1e-5
        dense = np.asarray(ref.route(p, g, sz))
        got = np.zeros_like(dense)
        np.put_along_axis(got, np.asarray(ids), np.asarray(w), axis=1)
        assert np.abs(got - dense).max() < 1e-6
        # NOT renormalised: six probabilities of 24 sum to less than 1
        assert (np.asarray(w).sum(-1) < 5.9).all()


def test_the_bias_selects_and_the_probability_weighs():
    """Four ids, two a token, logits (2, 1, 0, -1): by probability the picks
    are 0 and 1. A bias of +0.5 on id 2 puts it in (p_2 + 0.5 beats p_1); its
    WEIGHT is 6 p_2, without the bias, and nothing sums to 6."""
    share = ExpertShare(4, [0, 1], 2, 8, score="softmax_raw", route_scale=6.0,
                        policy=F32)
    p = dict(router=jnp.eye(4, dtype=jnp.float32),
             expert_bias=jnp.zeros((4,), jnp.float32))
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    probs = np.exp([2.0, 1.0, 0.0, -1.0]) / np.exp([2.0, 1.0, 0.0, -1.0]).sum()
    ids, w = share.route(p, x)
    assert sorted(np.asarray(ids[0])) == [0, 1]
    assert np.allclose(np.sort(np.asarray(w[0])), 6 * np.sort(probs[:2]))
    p["expert_bias"] = p["expert_bias"].at[2].set(0.5)
    ids, w = share.route(p, x)
    assert sorted(np.asarray(ids[0])) == [0, 2]
    assert np.allclose(np.sort(np.asarray(w[0])), 6 * np.sort(probs[[0, 2]]))
    # the other two scores say what they did
    soft = ExpertShare(4, [0, 1], 2, 8, policy=F32)
    _, w = soft.route(dict(router=jnp.eye(4, dtype=jnp.float32)), x)
    assert np.allclose(np.asarray(w).sum(), 1.0)
    with pytest.raises(ValueError, match="softmax_raw"):
        ExpertShare(4, [0], 2, 8, score="raw")


def test_a_pick_on_a_zero_compute_id_adds_its_weight_times_the_input():
    """Router of 4 + 2: ids 4 and 5 are identity experts. A token sent to id
    4 and to the held expert 0 gets ``w_0 FFN_0(x) + w_4 x``; one sent to 4
    and 5 gets ``(w_4 + w_5) x`` and touches no weight; a dead token gets
    nothing and counts nowhere."""
    share = ExpertShare(4, [0, 1], 2, 8, zero_experts=2, score="softmax_raw",
                        route_scale=6.0, policy=F32)
    params = share.init(jax.random.PRNGKey(0), (1, 6))["params"]
    assert params["router"].shape == (6, 6) \
        and params["expert_bias"].shape == (6,)
    params["router"] = jnp.eye(6, dtype=jnp.float32) * 4.0
    x = jnp.asarray([[1.0, 0, 0, 0, 1.0, 0],        # ids 0 and 4
                     [0, 0, 0, 0, 1.0, 1.0],        # ids 4 and 5
                     [0, 0, 0, 0, 1.0, 1.0]])       # dead
    live = jnp.asarray([True, True, False])
    ids, w = share.route(params, x)
    assert sorted(np.asarray(ids[0])) == [0, 4] \
        and sorted(np.asarray(ids[1])) == [4, 5]
    with collect_counts() as sink:
        y, counts = share.routed(params, x, live)
    w0 = float(w[0][list(np.asarray(ids[0])).index(0)])
    w4 = float(w[0][list(np.asarray(ids[0])).index(4)])
    ffn = (jax.nn.silu(x[0] @ params["gate"][0].T)
           * (x[0] @ params["up"][0].T)) @ params["down"][0]
    assert np.allclose(np.asarray(y[0]), w0 * np.asarray(ffn)
                       + w4 * np.asarray(x[0]), atol=1e-5)
    assert np.allclose(np.asarray(y[1]), float(w[1].sum()) * np.asarray(x[1]),
                       atol=1e-6)
    assert not np.asarray(y[2]).any()
    # held counts; in a list of their own the zero picks of LIVE tokens and
    # the most real experts a live token picked
    assert list(np.asarray(counts)) == [1, 0] and not sink
    assert [list(np.asarray(z)) for z in sink.zero] == [[3, 1]]
    assert share._config()["zero_experts"] == 2


def test_the_seeded_bias_moves_a_selection(sz, weights):
    """The reference's weights: selection by p + bias differs from selection
    by p for a good share of tokens, and the zero-compute ids take about
    their third of the picks."""
    p = weights[1]["h0"]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(11), (512, 64), jnp.float32)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True))
    with_bias = np.asarray(ref.route(p, g, sz)) > 0
    no_bias = np.asarray(ref.route(
        dict(p, expert_bias=jnp.zeros_like(p["expert_bias"])), g, sz)) > 0
    moved = (with_bias != no_bias).any(axis=1).mean()
    assert 0.01 < moved < 0.9       # 24 ids: p of 0.04 to 0.2 beside 0.0005
    assert 0.25 < with_bias[:, 16:].sum() / with_bias.sum() < 0.42
    bias = np.asarray(p["expert_bias"]).reshape(-1, ref.BIAS_BLOCK)
    assert (np.abs(bias) == np.float32(ref.BIAS)).all() \
        and (bias.sum(axis=1) == 0).all()
    cols = np.asarray(p["router"])
    assert np.allclose(np.linalg.norm(cols, axis=0), ref.ROUTER_COLUMN_NORM,
                       rtol=1e-2)
    assert (cols[:, 1::2] == -cols[:, 0::2]).all()


# -- (3) the shares add up ---------------------------------------------------------

@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_and_the_identity_term_once_are_the_uncut_layer(chips):
    """Sixteen experts on ``chips`` chips: the routed parts of the shares
    (each by the PROGRAM's layer told which it holds, WITHOUT its identity
    term) plus the identity term once are the reference's UNCUT layer. What
    a share leaves out is exactly what the others add; the identity experts
    live on no chip: every chip adds them for its OWN rows."""
    whole = ref.sizes_of(dict(CFG, n_routed_experts=16, published={}))
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                               ref.make_params(whole, 7))["h1"]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(9), (96, 64), jnp.float32)
    want = ref.experts(p, g, whole) + ref.identity_term(p, g, whole)
    n = 16 // chips
    total, counted, zero_picks = jnp.zeros_like(g), 0, None
    for k in range(chips):
        held = range(n * k, n * k + n)
        share = ExpertShare(held=held, policy=F32, **SHARE)
        mine = dict(p, **{name: p[name][n * k:n * k + n]
                          for name in ("gate", "up", "down")})
        with collect_counts() as sink:
            y, counts = share.routed(mine, g)
        # this chip's rows: its held experts' part and the identity term
        part = ref.experts(mine, g, whole, which=held)
        ident = ref.identity_term(p, g, whole)
        assert np.abs(np.asarray(y) - np.asarray(part + ident)).max() < 1e-5
        total += y - ident
        counted += int(counts.sum())
        zero_picks, most = (int(v) for v in sink.zero[0])
        assert counts.shape == (n,) and 0 < most <= 6
    total += ident
    # every pick once: on a real expert (one chip's count) or a zero one
    assert counted + zero_picks == 96 * 6
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


def test_every_token_to_one_expert_drops_nothing(sz, weights):
    share = ExpertShare(held=range(8), policy=F32, **SHARE)
    p = dict(weights[1]["h0"]["moe"])
    p["router"] = p["router"].at[:, 3].set(0.0).at[0, 3].set(50.0)
    g = jax.random.normal(jax.random.PRNGKey(4), (300, 64), jnp.float32)
    g = g.at[:, 0].set(jnp.abs(g[:, 0]) + 1.0)
    y, counts = share.routed(p, g)
    assert int(counts[3]) == 300
    want = ref.experts(p, g, sz) + ref.identity_term(p, g, sz)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-4


@pytest.mark.parametrize("tokens, bound, parts", [
    (96, 96 * 6 * 64 * 4, 1), (96, 96 * 6 * 64 * 4 - 1, 2),
    (96, 6 * 64 * 4 * 10, 12), (7, 1, 7)])
def test_a_step_past_the_sorted_bound_goes_through_in_slices(
        sz, weights, monkeypatch, tokens, bound, parts):
    """The sorted buffer has room for every assignment; past
    ``SORTED_BYTES`` the tokens take the held experts a slice at a time (the
    fewest equal slices under the bound): the same sums, counts and zero
    counters as the whole step's, a dead token counted nowhere."""
    share = ExpertShare(held=range(8), policy=F32, **SHARE)
    p = weights[1]["h0"]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(6), (tokens, 64), jnp.float32)
    live = jnp.arange(tokens) % 5 != 2
    before = jax.jit(share.routed).lower(p, g, live).as_text()
    with collect_counts() as whole:
        y, counts = share.routed(p, g, live)
    monkeypatch.setattr(moe_lib, "SORTED_BYTES", bound)
    text = jax.jit(share.routed).lower(p, g, live).as_text()
    with collect_counts() as cut:
        y2, counts2 = share.routed(p, g, live)
    assert (text == before) == (parts == 1)
    assert (f"tensor<{parts}x{tokens // parts}x64xf32>" in text) \
        == (parts > 1)
    assert np.abs(np.asarray(y2) - np.asarray(y)).max() < 1e-5
    assert list(np.asarray(counts2)) == list(np.asarray(counts))
    assert [list(np.asarray(z)) for z in cut.zero] \
        == [list(np.asarray(z)) for z in whole.zero]


# -- (4) the pool: two cache layers a block -----------------------------------------

def test_the_pool_is_sized_by_cache_layers_not_blocks(model):
    assert (model.num_layers, model.cache_layers) == (2, 4)
    where = model._paged_layers(None, "tables")
    assert [w["layer"] for w in where] == [(0, 1), (2, 3)]
    assert all(w["block_tables"] == "tables" for w in where)
    # every other model's answer is what it was
    for name, layers in (("gpt2_tiny", None), ("mistral_small4_tiny", 2),
                         ("evabyte_tiny", 2), ("trinity_large_tiny", 5)):
        other = models.create(name)
        assert other.cache_layers == other.num_layers == (
            layers or other.num_layers)
    plain = models.create("mistral_small4_tiny")
    assert [w["layer"] for w in plain._paged_layers(None, None)] == [0, 1]


def test_a_block_is_a_page_of_every_cache_layer_to_the_last_token(
        model, weights):
    """A block of the pool is one page of ALL four cache layers: two requests
    of 8 + 120 positions need 16 blocks each to their last token, and 32
    allocatable hold both with nothing preempted; one block fewer and the
    pool is too small for the pair (a latent pool admits by the first step
    and preempts; the cell's pool holds every request to its last token)."""
    eng = engine(model, weights[1], num_blocks=33, max_seq_len=128)
    assert eng.pool.page_shape[0] == 4
    assert eng.pool.kv_bytes_per_token == 4 * 128 * 4
    assert eng.pool.lifetime_blocks(128) == 16 == eng.pool.capacity // 2
    p = np.arange(8, dtype=np.int32)
    a, b = eng.submit(p, 120), eng.submit(p + 1, 120)
    out = eng.run_until_complete()
    eng.check_invariants()
    assert len(out[a]) == len(out[b]) == 120
    assert eng.metrics.summary()["preemptions"] == 0
    assert eng.metrics.summary()["pool_occupancy_max"] == 1.0
    assert eng.pool.num_allocated == 0 and eng.pool.num_free == 32
    small = engine(model, weights[1], num_blocks=32, max_seq_len=128)
    ra, rb = small.submit(p, 120), small.submit(p + 1, 120)
    got = small.run_until_complete()
    assert small.metrics.summary()["preemptions"] >= 1
    assert (got[ra], got[rb]) == (out[a], out[b])   # recomputed, the same
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        small.submit(p, 180)


@pytest.mark.parametrize("how", ["finish", "cancel", "preempt"])
def test_no_page_leaks(model, weights, how):
    eng = engine(model, weights[1], max_batch_size=2)
    p = np.random.default_rng(3).integers(0, 256, 30).astype(np.int32)
    rid = eng.submit(p, 40)
    for _ in range(25):
        eng.step()
    req = eng.requests[rid]
    assert eng.pool.num_allocated == len(req.block_table) > 0
    eng.check_invariants()
    if how == "cancel":
        eng.cancel(rid)
    elif how == "preempt":
        eng._preempt(req)
        assert req.block_table == [] and eng.pool.num_allocated == 0
    out = eng.run_until_complete()
    eng.check_invariants()
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free == eng.pool.capacity
    if how != "cancel":
        clean = engine(model, weights[1])
        want = clean.submit(p, 40)
        assert out[rid] == clean.run_until_complete()[want]


# -- (5) counters and scopes ----------------------------------------------------------

def test_step_programs_return_the_counters_with_the_tokens(model, weights):
    """One fetch a step: (layers, held + 2) counts ride beside the tokens,
    and the window's summary folds them."""
    eng = engine(model, weights[1], overlap=True)
    p = np.random.default_rng(2).integers(0, 256, 21).astype(np.int32)
    for n in (21, 10, 16):
        eng.submit(p[:n], 12)
    eng.run_until_complete()
    s, m = eng.metrics.summary(), eng.metrics
    layers, top_k = 2, 6
    tokens = s["decode_tokens"] + s["prefill_tokens"]
    assert m.expert_assignments == tokens * top_k * layers
    assert m.expert_layer_tokens == tokens * layers
    # held over ALL picks (the zero-compute ones too)
    assert s["expert_held_share"] == m.expert_held_assignments \
        / m.expert_assignments
    assert 0.15 < s["expert_held_share"] < 0.5      # 8 of 24 on the mean
    assert 0.2 < s["zero_pick_share"] < 0.5         # 8 of 24 on the mean
    assert s["ffn_picks_per_token_mean"] == pytest.approx(
        top_k * (1 - s["zero_pick_share"]))
    assert 1 <= s["ffn_picks_max_over_mean"] <= top_k
    assert 0 < s["experts_hit_share"] <= 1
    assert s["expert_load_max_over_mean"] >= 1
    with collect_counts() as counts:        # the program's own extra output
        model.apply_paged(
            weights[1], jnp.zeros((2, 4), jnp.int32), eng.pool.pages_k,
            eng.pool.pages_v, jnp.zeros((2, eng.blocks_per_seq), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([4, 0]))
    assert len(counts) == len(counts.zero) == layers \
        and counts[0].shape == (8,)
    held, (zero, most) = counts[0], counts.zero[0]
    assert int(held.sum()) + int(zero) <= 4 * top_k     # the dead row: none
    assert 0 < int(most) <= top_k
    # a model without zero-compute experts reports none of the three
    other = models.create("mistral_small4_tiny")
    e2 = engine(other, other.init(jax.random.PRNGKey(0), (1, 8))["params"])
    e2.submit(p[:9], 4)
    e2.run_until_complete()
    s2 = e2.metrics.summary()
    assert "expert_held_share" in s2 and "zero_pick_share" not in s2 \
        and "ffn_picks_per_token_mean" not in s2


def test_the_scopes_split_a_block_into_its_parts(model, weights):
    """``h<i>/a0`` and ``h<i>/a1`` around the two halves, each with the
    attention scopes and ``mlp`` of a Llama block; ``moe_route``,
    ``moe_experts`` and ``moe_zero`` under ``a0`` (where the experts are
    computed) and the shortcut's add under ``a1/moe_shortcut``."""
    pool = PagedKVPool(4, 1, model.latent_row, 16, 8, dtype=jnp.float32,
                       latent=True)
    def lowered():      # a new function a call: no trace is found again
        return jax.jit(lambda *a: model.apply_decode_paged(*a)).lower(
            weights[1], jnp.zeros((2,), jnp.int32), pool.pages_k,
            pool.pages_v, jnp.zeros((2, 8), jnp.int32),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)

    # on the chip a half's write is the row-tile kernel, under its scope
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "1"}):
        text = lowered()
    for i in range(2):
        for half in ("a0", "a1"):
            assert f"h{i}/{half}/kv_write/jit(_write_rows_pallas)" in text
            assert f"h{i}/{half}/mla_attn/tnn_mla_attention/" in text
    assert '"tnn_kv_row_write/' in text
    text = lowered()
    assert "tnn_kv_row_write" not in text       # off the chip: whole pages
    for i in range(2):
        for half in ("a0", "a1"):
            for scope in ("attn_qkv", "mla_attn", "kv_write", "attn_out",
                          "mlp"):
                assert f"h{i}/{half}/{scope}" in text, (i, half, scope)
        for scope in ("moe_route", "moe_experts", "moe_zero"):
            assert f"h{i}/a0/{scope}" in text
            assert f"h{i}/a1/{scope}" not in text
        assert f"h{i}/a1/moe_shortcut" in text \
            and f"h{i}/a0/moe_shortcut" not in text
    assert "moe_shared" not in text and "h2/" not in text


# -- (6) the rank scales and the plain rotary ---------------------------------------

def test_rank_scales_fold_into_the_norms_and_rotary_is_plain(model, sz):
    attn = model.blocks[0].halves[0].attn
    assert (attn.q_scale, attn.kv_scale) == pytest.approx(ref.rank_scales(sz))
    assert attn.kv_scale == pytest.approx((64 / 32) ** 0.5)
    plain = (10000.0 ** (-np.arange(0, 16, 2, dtype=np.float64) / 16)
             ).astype(np.float32)
    assert (attn.inv_freq == plain).all() and attn.scale == 32 ** -0.5
    # YaRN at factor 1 is the same frequencies: the two paths meet
    assert (yarn_inv_freq(16, 10000.0, 1.0, 32, 32, 1) == plain).all()
    assert (yarn_inv_freq(64, 1e7, 1.0, 1 << 30, 32, 1) == (1e7 ** (
        -np.arange(0, 64, 2, dtype=np.float64) / 64)).astype(np.float32)).all()
    # the cached row is the SCALED normed latent
    kw = dict(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=16, v_dim=16,
              rope=dict(rope_theta=10000.0), policy=F32)
    one, two = LatentAttention(4, **kw), LatentAttention(4, kv_scale=2.0,
                                                         q_scale=3.0, **kw)
    p = one.init(jax.random.PRNGKey(1), (1, 4, 64))["params"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 64), jnp.float32)
    q1, c1, r1 = one._latents(p, x, 0)
    q2, c2, r2 = two._latents(p, x, 0)
    assert np.allclose(np.asarray(c2), 2.0 * np.asarray(c1), rtol=1e-6)
    assert np.allclose(np.asarray(q2), 3.0 * np.asarray(q1), rtol=1e-5,
                       atol=1e-5)
    assert (np.asarray(r1) == np.asarray(r2)).all()     # k_rope: unscaled
    assert two._config()["kv_scale"] == 2.0 and "kv_scale" not in \
        one._config()


# -- (7) the refusals -----------------------------------------------------------------

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


def test_one_refusal_function_and_the_other_three_read_as_before(model):
    msg = refuse_windowed(model, host_tier_bytes=1 << 20)
    assert "host KV tier" in msg and "latent row" in msg
    assert refuse_windowed(model) is None
    eva = refuse_windowed(models.create("evabyte_tiny"), prefix_cache=True)
    assert "exact window of 32" in eva and "latent" not in eva
    assert eva.endswith("a cached block would have to carry the summaries "
                        "of everything before it")
    lat = refuse_windowed(models.create("mistral_small4_tiny"), spec=True)
    assert lat.endswith("is not held against the reference over latent "
                        "pages")
    tri = refuse_windowed(models.create("trinity_large_tiny"), tp=2)
    assert "two groups of page" in tri and "a window of 16" in tri


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
        serve.main(["--model", "longcat_flash_tiny", *flags])
    assert e.value.code == 2 and what in err.getvalue()
    assert "random-weight" not in err.getvalue()


# -- (8) the served model, as published ----------------------------------------------

def test_the_published_sizes_of_the_served_model():
    """``longcat_flash_ep32`` as the cell runs it: every width the source's."""
    m = models.create("longcat_flash_ep32")
    assert (m.num_layers, m.cache_layers, m.d_model, m.num_heads,
            m.mlp_hidden, m.vocab_size) == (4, 8, 6144, 64, 12288, 16384)
    assert m.latent_row == 640 and m.num_kv_heads == 1
    attn = m.blocks[0].halves[1].attn
    assert (attn.q_rank, attn.kv_rank, attn.nope_dim, attn.rope_dim,
            attn.v_dim) == (1536, 512, 128, 64, 128)
    assert (attn.q_scale, attn.kv_scale) == (2.0, 12 ** 0.5)
    assert attn.scale == 192 ** -0.5 and attn.inv_freq[0] == 1.0
    moe = m.blocks[3].moe
    assert (moe.num_experts, moe.zero_experts, moe.width, moe.top_k,
            moe.hidden, len(moe.held), moe.shared) == (512, 256, 768, 12,
                                                       2048, 16, 0)
    assert (moe.score, moe.route_scale) == ("softmax_raw", 6.0)
    shapes = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), (1, 8))["params"])
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 5.173e9) < 0.001e9       # 10.35 GB of bf16
    assert shapes["h1"]["moe"]["router"].shape == (6144, 768)
    assert shapes["h1"]["moe"]["expert_bias"].dtype == jnp.float32
    assert shapes["h1"]["a1"]["up"]["kernel"].shape == (6144, 12288)
    assert shapes["h1"]["a0"]["attn"]["kv_a_kernel"].shape == (6144, 576)
    cfg = m._config()
    assert cfg["shortcut"] is True and cfg["num_layers"] == 4
    with pytest.raises(ValueError, match="given its experts"):
        models.create("longcat_flash_tiny", experts=None)
