"""Trinity Large's block on the normal serving path (PR 37): sliding-window
layers beside global layers in ONE model over two groups of page from one
allocator, gated grouped-query attention with a norm on every query and key
head, a sigmoid router with a selection bias. Tiny sizes on the CPU (5 layers
in the published order: a dense sliding layer, then sliding, sliding, full,
sliding; 64 wide, 4 query heads over 2 KV heads of 32, window 16, 8 of 16
experts of width 32 held, 4 a token, one shared), seeded weights, logits held
against ``chipbench/reference/afmoe.py``: the same module the benchmark
compares with, which imports nothing of the program."""
import contextlib
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import afmoe as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.nn.moe import ExpertShare
from tnn_tpu.ops.pallas import paged_attention as pa
from tnn_tpu.serving import InferenceEngine, step_build
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving.kv_pool import PagedKVPool
from tnn_tpu.serving.scheduler import Request

KINDS = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
CFG = dict(num_hidden_layers=5, num_dense_layers=1, hidden_size=64,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           intermediate_size=128, moe_intermediate_size=32,
           num_experts_per_tok=4, num_shared_experts=1, sliding_window=16,
           vocab_size=256, max_position_embeddings=256, served_positions=256,
           rms_norm_eps=1e-5, rope_theta=10000.0, route_scale=2.448,
           mup_enabled=True, layer_types=KINDS, num_experts=8,
           published={"num_experts": 16}, score_func="sigmoid",
           route_norm=True, n_group=1, rope_scaling=None)
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The program in float32 (pages of two groups, a lower bound in the kernel's
# walk, sorted experts) against the float32 reference at precision "highest"
# (one square mask, no cache, experts one at a time): what is left is the
# order of sums. Logits of a model 64 wide are O(1); 2e-4 is a hundred
# float32 steps of them. The SAME program in bfloat16 misses it by two orders
# of magnitude (asserted below).
TOL = 2e-4
GROUPS = dict(window=16, full_layers=1, window_layers=4)


@pytest.fixture(scope="module")
def sz():
    return ref.sizes_of(CFG)


@pytest.fixture(scope="module")
def weights(sz):
    p = ref.make_params(sz, 37)
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(sz):
    m = models.create("trinity_large_tiny", policy=F32)
    ref.check_program(m, sz, "trinity_large_tiny")
    return m


@pytest.fixture(scope="module")
def forward(weights, sz):
    return ref.Forward(weights[0], sz, 128)


def engine(model, params, **kw):
    kw = dict(dict(num_blocks=128, block_size=8, max_batch_size=4,
                   chunk_size=16, prefix_cache=False, max_seq_len=192), **kw)
    return InferenceEngine(model, params, **kw)


# -- (a) logits through the two page groups against the reference -------------

class _Row:
    """One request's tables, grown and given back as the engine does it
    (``_grow_need`` / ``_extend`` / ``_end_window``), by the pool's own
    arithmetic."""

    def __init__(self, pool):
        self.pool, self.req = pool, Request(0, np.zeros(1, np.int32), 1)
        self.released = 0

    def grow(self, at, n):
        pool, req = self.pool, self.req
        need = pool.table_need(at, n)[0] - len(req.block_table)
        req.block_table += pool.alloc(max(0, need))
        need = pool.window_need(at, n, req.window_base) \
            - len(req.window_table)
        req.window_table += pool.alloc(max(0, need))

    def commit(self, at):
        pool, req = self.pool, self.req
        drop = (pool.release_behind(at) - req.window_base) \
            * pool.window_layers
        if drop > 0:
            pool.free(req.window_table[:drop])
            del req.window_table[:drop]
            req.window_base += drop // pool.window_layers
            self.released += drop
        pool.check_invariants([req.block_table], [at], None,
                              [req.window_table], [req.window_base])

    def packed(self, width, rows=2, row=1):
        step = step_build.DecodeStep(
            key=(), b=rows, nb=width, tables=np.zeros((rows, width), np.int32),
            temps=np.zeros(rows, np.float32), topks=np.zeros(rows, np.int32),
            topps=np.zeros(rows, np.float32),
            poison=np.zeros(rows, np.float32))
        step_build._fill_row(step, row, self.req, 0, self.pool.kinds)
        return jnp.asarray(step.tables)


def _paged_logits(model, params, ids, n_prompt, chunk, bs=8):
    """Chunked prefill (ragged: the last chunk is short) and then decode, one
    sequence in row 1 of a batch of 2 (row 0 is padding), straight through
    ``apply_paged`` / ``apply_decode_paged`` over ONE layer of pages of two
    groups: logits at every position, and the row's bookkeeping."""
    pool = PagedKVPool(1, 2, 32, 80, bs, dtype=jnp.float32, groups=GROUPS)
    assert pool.page_shape == (1, 80, 1, bs, 64)    # two heads of 32 a row
    assert pool.win_pages == 16 // bs + 2
    width = pool.table_width(len(ids))
    row = _Row(pool)
    pk, pv = pool.pages_k, pool.pages_v
    out, at = [], 0
    apply_paged = jax.jit(model.apply_paged)
    apply_decode_paged = jax.jit(model.apply_decode_paged)
    while at < n_prompt:
        n = min(chunk, n_prompt - at, pool.room_in_window(at))
        toks = np.zeros((2, chunk), np.int32)
        toks[1, :n] = ids[at:at + n]
        row.grow(at, n)
        lg, pk, pv = apply_paged(
            params, jnp.asarray(toks), pk, pv, row.packed(width),
            jnp.asarray([0, at]), jnp.asarray([0, n]))
        out.append(np.asarray(lg[1, :n]))
        at += n
        row.commit(at)
    for t in range(n_prompt, len(ids)):
        row.grow(t, 1)
        lg, pk, pv = apply_decode_paged(
            params, jnp.asarray([0, ids[t]]), pk, pv, row.packed(width),
            jnp.asarray([0, t]))
        out.append(np.asarray(lg[1:2]))
        row.commit(t + 1)
    return np.concatenate(out), row


@pytest.mark.parametrize("n_prompt,chunk", [(37, 16), (9, 8), (16, 16),
                                            (61, 5)])
def test_chunked_prefill_then_decode_match_the_reference(
        model, weights, forward, n_prompt, chunk):
    """Prompts under (9), at (16) and over (37, 61) the window of 16, decoded
    to 90 positions: every row crosses the window, gives window pages back
    inside the test, and never holds more than ``win_pages`` a layer."""
    ids = np.random.default_rng(n_prompt).integers(0, 256, 90).astype(
        np.int32)
    want = forward.rows(list(ids), np.arange(90))
    got, row = _paged_logits(model, weights[1], ids, n_prompt, chunk)
    assert np.abs(got - want).max() < TOL
    pool, req = row.pool, row.req
    assert row.released > 0 and req.window_base == (90 - 16 + 1) // 8
    assert len(req.window_table) <= 4 * pool.win_pages
    assert len(req.block_table) == pool.blocks_for(90)
    assert pool.num_allocated == len(req.block_table) + len(req.window_table)


def test_the_plain_forward_and_bf16(model, weights, forward):
    ids = np.random.default_rng(5).integers(0, 256, 90).astype(np.int32)
    want = forward.rows(list(ids), np.arange(90))
    full, _ = model.apply({"params": weights[1], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full[0]) - want).max() < TOL
    # a bf16 program does not pass this tolerance: it is a float32 one
    low = models.create("trinity_large_tiny", policy=DTypePolicy(
        io="bfloat16", param="bfloat16", compute="bfloat16"))
    full16, _ = low.apply({"params": weights[0], "state": {}},
                          jnp.asarray(ids)[None])
    assert np.abs(np.asarray(full16[0], np.float32) - want).max() > 50 * TOL


@pytest.mark.parametrize("drop", ["window", "rope_on_global", "gate",
                                  "qk_norm", "post_norm", "embed_scale"])
def test_the_comparison_sees_each_mechanism(model, weights, forward, drop):
    """Each piece of the block, left out of the reference, moves the logits
    far past the tolerance: the comparison above holds every one of them."""
    ids = np.random.default_rng(11).integers(0, 256, 64).astype(np.int32)
    sz = dict(forward.sz)
    p = jax.tree_util.tree_map(lambda x: x, weights[0])
    if drop == "window":
        sz["sliding_window"] = 1 << 20
    elif drop == "rope_on_global":
        sz["layer_types"] = ["sliding_attention"] * 5
        sz["sliding_window"] = 1 << 20
    elif drop == "embed_scale":
        sz["mup_enabled"] = False
    else:
        for i in range(5):
            a = p[f"h{i}"]["attn"]
            if drop == "gate":      # sigmoid(40) = 1: no gate
                a["qkvg_kernel"] = a["qkvg_kernel"].at[:, -128:].set(0.0) \
                    .at[0, -128:].set(40.0)
            elif drop == "qk_norm":
                a["q_norm"] = a["q_norm"] * 3.0
            else:
                p[f"h{i}"]["ln1_post"]["scale"] = \
                    p[f"h{i}"]["ln1_post"]["scale"] * 2.0
    other = ref.Forward(p, sz, 64).rows(list(ids), np.arange(64))
    got, _ = model.apply({"params": weights[1], "state": {}},
                         jnp.asarray(ids)[None])
    assert np.abs(np.asarray(got[0]) - other).max() > 50 * TOL


@pytest.mark.parametrize("overlap", [False, True])
def test_the_engine_serves_the_reference_tokens(model, weights, forward,
                                                overlap):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (37, 5, 16, 61)]
    eng = engine(model, weights[1], overlap=overlap)
    rids = [eng.submit(p, 60) for p in prompts]
    out = eng.run_until_complete()
    eng.check_invariants()
    for p, rid in zip(prompts, rids):
        lg = forward.rows(list(p) + out[rid],
                          np.arange(len(p) - 1, len(p) + 59))
        assert (lg.argmax(-1) == np.asarray(out[rid])).all()
    s = eng.metrics.summary()
    assert eng.pool.num_allocated == 0 and s["preemptions"] == 0
    # four window layers, a page of 8 given back every 8 steps a row
    assert s["win_pages_released"] > 4 * 4 * 5
    assert 0.5 < s["win_fill_mean"] <= 1.0
    assert 0 < s["win_pool_occupancy_max"] < 1
    assert 0 < s["experts_hit_share"] <= 1
    assert {k[0] for k in eng._jit} == {"pdecode", "mixed"}


# -- (b) the kernel's lower bound ------------------------------------------------

def _dense(q, pages_k, pages_v, tables, kv_lens, q_lens, window, base):
    """Every row by hand: its pages laid out flat from position ``base *
    bs``, each query's scores over positions ``p - window + 1 .. p``."""
    b, qw, h, dh = q.shape
    _, _, hkv, bs, _ = pages_k.shape
    out = np.zeros(q.shape, np.float32)
    for r in range(b):
        k = np.asarray(pages_k[0][tables[r]]).transpose(1, 0, 2, 3) \
            .reshape(hkv, -1, dh)
        v = np.asarray(pages_v[0][tables[r]]).transpose(1, 0, 2, 3) \
            .reshape(hkv, -1, dh)
        for t in range(int(q_lens[r])):
            p = int(kv_lens[r]) - int(q_lens[r]) + t
            lo = max(0, p - window + 1) - int(base[r]) * bs
            hi = p - int(base[r]) * bs + 1
            for head in range(h):
                kh = head // (h // hkv)
                s = k[kh, lo:hi] @ np.asarray(q[r, t, head]) / np.sqrt(dh)
                w = np.exp(s - s.max())
                out[r, t, head] = (w / w.sum()) @ v[kh, lo:hi]
    return out


@pytest.mark.kernel
@pytest.mark.parametrize("qw,q_lens,kv_lens", [
    (1, [1, 1, 1, 1], [5, 16, 24, 41]),         # under, at, aligned, over
    (1, [1, 1, 1, 0], [17, 33, 47, 0]),         # straddling bounds, a dead row
    (4, [4, 2, 3, 4], [12, 33, 40, 20]),        # chunks: each query its bound
    (8, [8, 5, 1, 8], [9, 30, 44, 24])])
@pytest.mark.parametrize("lazy", [0, 1])
def test_the_lower_bound_matches_the_reference(qw, q_lens, kv_lens, lazy):
    """Grouped heads (4 over 2), pages of 4, a window of 10 (its bound lies
    inside a page more often than on its edge), decode rows and chunks.
    ``lazy``: the table still holds a page behind the window (given back
    only at the next commit): walked past, not read."""
    rng = np.random.default_rng(qw + sum(kv_lens))
    n, hkv, bs, dh, h, b, nb, window = 40, 2, 4, 8, 4, 4, 7, 10
    pk, pv = (jnp.asarray(rng.normal(size=(1, n, hkv, bs, dh)), jnp.float32)
              for _ in range(2))
    tables = rng.permutation(np.arange(1, n))[:b * nb].reshape(b, nb)
    first = [max(0, k - ql - window + 1) // bs
             for k, ql in zip(kv_lens, q_lens)]
    base = np.maximum(np.asarray(first) - lazy, 0)
    q = jnp.asarray(rng.normal(size=(b, qw, h, dh)), jnp.float32)
    kw = dict(q_lens=jnp.asarray(q_lens, jnp.int32), window=window,
              table_base=jnp.asarray(base, jnp.int32))
    args = (q, pk, pv, jnp.asarray(tables, jnp.int32),
            jnp.asarray(kv_lens, jnp.int32))
    want = pa.paged_attention_reference(*args, **kw)
    got = pa.paged_attention(*args, backend="pallas", interpret=True, **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    by_hand = _dense(q, pk, pv, tables, kv_lens, q_lens, window, base)
    assert np.abs(np.asarray(want) - by_hand).max() < 1e-5
    if qw == 1:     # the decode form (3-D queries) is the same launch
        kw3 = dict(kw, q_lens=None)
        got3 = pa.paged_attention(q[:, 0], *args[1:], backend="pallas",
                                  interpret=True, **kw3)
        live = np.asarray(q_lens) > 0
        assert np.abs(np.asarray(got3)[live]
                      - np.asarray(want[:, 0])[live]).max() < 1e-5


@pytest.mark.kernel
def test_a_windowed_launch_walks_a_windows_pages_not_the_table():
    """The grid's last axis is as long as the window's pages: a table of 64
    entries under a window of 16 positions in pages of 4 is walked 5 entries
    a row (7 for a chunk of 8), wherever the row stands; with no window the
    whole table."""
    assert pa.window_walk(16, 1, 4, 64) == 5    # 16 positions: 5 pages of 4
    assert pa.window_walk(16, 8, 4, 64) == (16 + 8 - 2) // 4 + 2
    assert pa.window_walk(4096, 1, 128, 290) == 33
    assert pa.window_walk(4096, 64, 128, 34) == 34
    assert pa.window_table_pages(4096, 128) == 34
    with pytest.raises(ValueError, match="whole pages"):
        pa.window_table_pages(100, 8)
    tables = jnp.arange(64, dtype=jnp.int32)[None].repeat(2, 0)
    lens = jnp.asarray([203, 9], jnp.int32)
    first = jnp.asarray([(203 - 1 - 15) // 4, 0], jnp.int32)
    walk = pa._fetch_table(tables, lens - first * 4, 4, 1, first, 6)
    assert walk.shape == (2, 6)
    assert walk[0].tolist() == [46, 47, 48, 49, 50, 50]     # dead: repeats
    assert walk[1].tolist() == [0, 1, 2, 2, 2, 2]
    # and the kernel carries the window in its NAME
    q = jnp.zeros((2, 1, 2, 8), jnp.float32)
    pages = jnp.zeros((1, 64, 2, 4, 8), jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: pa.paged_attention(
        *a, backend="pallas", interpret=False, window=16))(
        q, pages, pages, tables, lens))
    assert "tnn_paged_attention_win" in text
    plain = str(jax.make_jaxpr(lambda *a: pa.paged_attention(
        *a, backend="pallas", interpret=False))(q, pages, pages, tables,
                                                lens))
    assert "tnn_paged_attention" in plain and "_win" not in plain


# -- (c) the share ties to the model ------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Sixteen experts on eight chips of two: the routed parts of the eight
    shares (each by the PROGRAM's layer told which it holds, router and
    selection bias whole) plus the shared expert once are the reference's
    UNCUT layer. What a share leaves out is exactly what the others add."""
    whole = ref.sizes_of(dict(CFG, num_experts=16, published={}))
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                               ref.make_params(whole, 7))["h2"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(9), (96, 64), jnp.float32)
    want = ref.experts(p, m, whole) + ref.shared(p, m)
    kw = dict(shared=1, score="sigmoid", route_scale=2.448, policy=F32)
    total = jnp.zeros_like(m)
    counted = 0
    for k in range(8):
        held = range(2 * k, 2 * k + 2)
        share = ExpertShare(16, held, 4, 32, **kw)
        mine = dict(p, **{n: p[n][2 * k:2 * k + 2]
                          for n in ("gate", "up", "down")})
        y, counts = share.routed(mine, m)
        total += y
        counted += int(counts.sum())
        # and the reference given the same share says the same
        part = ref.experts(mine, m, whole, which=held)
        assert np.abs(np.asarray(y) - np.asarray(part)).max() < 1e-5
    total += ExpertShare(16, range(2), 4, 32, **kw).shared_out(p, m)
    assert counted == 96 * 4                # every assignment, once
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 2e-5


# -- (d) the router ---------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_top4_agree_with_the_reference(sz, weights, seed):
    share = ExpertShare(16, range(8), 4, 32, shared=1, score="sigmoid",
                        route_scale=2.448, policy=F32)
    m = jax.random.normal(jax.random.PRNGKey(seed), (256, 64), jnp.float32)
    for i in range(1, 5):
        p = weights[1][f"h{i}"]["moe"]
        ids, w = share.route(p, m)
        want = np.asarray(ref.route(p, m, sz))
        got = np.zeros_like(want)
        np.put_along_axis(got, np.asarray(ids), np.asarray(w), axis=1)
        assert not ((got > 0) != (want > 0)).any()
        assert np.abs(got - want).max() < 1e-6
        assert np.allclose(got.sum(1), 2.448, atol=1e-5)


def test_the_bias_selects_and_the_score_weighs():
    """Four experts, two a token. Scores 0.9, 0.8, 0.3, 0.2 with a bias of
    0.7 on the third: chosen are the first and the THIRD (0.9 and 0.3 + 0.7
    = 1.0 beat 0.8), weighted 0.9 and 0.3 over their sum, the bias nowhere in
    a weight. With the bias at zero: the first and the second."""
    share = ExpertShare(4, range(4), 2, 8, score="sigmoid", policy=F32)
    logit = np.log(np.array([0.9, 0.8, 0.3, 0.2])
                   / (1 - np.array([0.9, 0.8, 0.3, 0.2])))
    p = {"router": jnp.asarray(logit[None], jnp.float32),
         "expert_bias": jnp.asarray([0.0, 0.0, 0.7, 0.0])}
    x = jnp.ones((1, 1), jnp.float32)
    ids, w = share.route(p, x)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 2]
    by_id = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(w[0]).tolist()))
    assert by_id[0] == pytest.approx(0.9 / 1.2, abs=1e-6)
    assert by_id[2] == pytest.approx(0.3 / 1.2, abs=1e-6)
    ids0, w0 = share.route(dict(p, expert_bias=jnp.zeros(4)), x)
    assert sorted(np.asarray(ids0[0]).tolist()) == [0, 1]
    assert np.asarray(w0[0]).sum() == pytest.approx(1.0, abs=1e-6)
    # the softmax router is as it was: no bias leaf, the weights sum to 1
    soft = ExpertShare(4, range(4), 2, 8, policy=F32)
    assert "expert_bias" not in soft.init(jax.random.PRNGKey(0),
                                          (1, 8))["params"]
    assert soft._config().get("score") is None


def test_the_seeded_bias_moves_a_selection(sz, weights):
    """The weights the benchmark serves: the bias is small and non-zero, and
    some token's experts differ from what its scores alone would choose."""
    share = ExpertShare(16, range(8), 4, 32, shared=1, score="sigmoid",
                        route_scale=2.448, policy=F32)
    p = weights[1]["h1"]["moe"]
    assert float(jnp.abs(p["expert_bias"]).max()) == pytest.approx(ref.BIAS)
    assert float(p["expert_bias"].sum()) == 0.0      # balanced signs
    m = jax.random.normal(jax.random.PRNGKey(3), (512, 64), jnp.float32)
    ids, _ = share.route(p, m)
    ids0, _ = share.route(dict(p, expert_bias=jnp.zeros(16)), m)
    moved = (np.sort(np.asarray(ids), 1) != np.sort(np.asarray(ids0), 1)) \
        .any(1).mean()
    assert 0 < moved < 0.5


# -- (e) admission over both groups, and no leak ---------------------------------------

def test_the_pool_counts_both_groups_to_the_last_token():
    pool = PagedKVPool(1, 2, 32, 64, 8, dtype=jnp.float32, groups=GROUPS)
    assert (pool.full_layers, pool.window_layers, pool.win_pages) == (1, 4, 4)
    # 100 positions: 13 global pages, and at most 4 window pages a layer
    assert pool.lifetime_blocks(100) == 13 + 4 * 4
    assert pool.lifetime_blocks(20) == 3 + 4 * 3
    assert pool.admission_blocks(8, 100) == pool.lifetime_blocks(100)
    assert pool.table_width(100) == 13 + 4 * 4 + 1
    assert pool.token_capacity == (63 - 16) * 8
    # what a step may write before pages must go back: at least bs + 2
    assert min(pool.room_in_window(n) for n in range(200)) == 8 + 2
    assert pool.release_behind(15) == 0 and pool.release_behind(23) == 1
    assert pool.window_need(37, 1, pool.release_behind(37)) == 4 * 3
    assert pool.table_need(37, 1) == (5, 0)
    for bad in (dict(window=32, chunk=4), dict(latent=True), dict(sp=2),
                dict(kv_dtype="int8"), dict(num_layers=5),
                dict(groups=dict(GROUPS, full_layers=0))):
        args = dict(dict(num_layers=1, num_kv_heads=2, head_dim=32,
                         num_blocks=16, block_size=8, groups=GROUPS), **bad)
        with pytest.raises(ValueError):
            PagedKVPool(**args)


def test_admission_waits_for_room_to_the_last_token(model, weights):
    """Two requests of 8 + 120 positions need 16 + 16 blocks each to their
    last token; 47 allocatable hold one and not two: the second waits for
    the first to end, and nothing is ever preempted."""
    eng = engine(model, weights[1], num_blocks=48, max_seq_len=128)
    need = eng.pool.lifetime_blocks(128)
    assert need == 16 + 4 * 4 and 2 * need > eng.pool.capacity >= need
    p = np.arange(8, dtype=np.int32)
    a, b = eng.submit(p, 120), eng.submit(p + 1, 120)
    eng.step()
    assert [r.rid for r in eng.scheduler.running] == [a]
    assert [r.rid for r in eng.scheduler.waiting] == [b]
    out = eng.run_until_complete()
    eng.check_invariants()
    assert len(out[a]) == len(out[b]) == 120
    assert eng.metrics.summary()["preemptions"] == 0
    assert eng.pool.num_allocated == 0 and eng.pool.num_free == 47
    # the longest request a pool serves is what fits it alone, both groups
    small = engine(model, weights[1], num_blocks=24, max_seq_len=192)
    assert small.max_seq_len == small.pool.token_capacity == (23 - 16) * 8
    with pytest.raises(ValueError, match="exceeds max_seq_len 56"):
        small.submit(p, 180)


@pytest.mark.parametrize("how", ["finish", "cancel", "preempt"])
def test_no_window_page_leaks(model, weights, how):
    """However a request leaves (its last token, a cancel, a preemption that
    recomputes it), both groups' blocks go back, the window's base with
    them, and a preempted request's stream is the one it would have had."""
    eng = engine(model, weights[1], max_batch_size=2)
    p = np.random.default_rng(3).integers(0, 256, 30).astype(np.int32)
    rid = eng.submit(p, 40)
    for _ in range(25):
        eng.step()
    req = eng.requests[rid]
    assert req.window_base > 0 and req.window_table and req.block_table
    held = len(req.block_table) + len(req.window_table)
    assert eng.pool.num_allocated == held
    eng.check_invariants()
    if how == "cancel":
        eng.cancel(rid)
    elif how == "preempt":
        eng._preempt(req)
        assert (req.block_table, req.window_table, req.window_base) \
            == ([], [], 0)
        assert eng.pool.num_allocated == 0
    out = eng.run_until_complete()
    eng.check_invariants()
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free == eng.pool.capacity
    if how != "cancel":
        want = engine(model, weights[1]).submit(p, 40)
        clean = engine(model, weights[1])
        clean.submit(p, 40)
        assert out[rid] == clean.run_until_complete()[want]


def test_a_page_not_given_back_is_found(model, weights):
    eng = engine(model, weights[1])
    rid = eng.submit(np.arange(30, dtype=np.int32), 40)
    for _ in range(20):
        eng.step()
    req = eng.requests[rid]
    req.window_base -= 1        # as if the last release had not happened
    with pytest.raises(ValueError, match="from page"):
        eng.check_invariants()


def test_deep_speculation_rides_over_releases(model, weights, monkeypatch):
    """A queue of decode steps deeper than a page: releases at the head of a
    window table while extensions wait at its tail to be adopted or rolled
    back. The tokens are the synchronous loop's."""
    from tnn_tpu.serving import engine as engine_lib

    monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", 1)
    monkeypatch.setattr(engine_lib, "SPECULATE_AHEAD_S", 3600.0)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (21, 40)]

    def run(**kw):
        eng = engine(model, weights[1], **kw)
        rids = [eng.submit(p, 70) for p in prompts]
        late = None
        for i in range(400):
            if not (eng.has_work or eng.in_flight):
                break
            if eng.overlap:
                if eng.in_flight is None:
                    eng.begin_step()
                while eng.try_speculate():
                    pass
                eng.finish_step()
            else:
                eng.step()
            if i == 30:         # an arrival rolls the queue back
                late = eng.submit(prompts[0][:7], 9)
        eng.run_deferred()
        eng.check_invariants()
        assert eng.pool.num_allocated == 0
        return [eng.requests[r].out_tokens for r in rids + [late]], eng

    off, _ = run(overlap=False)
    on, eng = run(overlap=True)
    assert on == off
    assert eng.metrics.summary()["overlap_rebuilds"] >= 1


# -- spans and counters -------------------------------------------------------------------------

def test_blocks_held_by_kind_are_the_tables_and_releases_are_instants(
        model, weights):
    eng = engine(model, weights[1], trace=True)
    eng.submit(np.arange(20, dtype=np.int32), 30)
    for _ in range(6):
        eng.step()
    # the blocks held by kind are what the pool and the requests' two tables
    # say (``win_pool_occupancy_max`` / ``pool_occupancy_max`` count them):
    # no span walks the rows' tables to say it again at every dispatch
    rows = eng.scheduler.running
    full = sum(len(r.block_table) for r in rows)
    window = sum(len(r.window_table) for r in rows)
    assert full > 0 and window > 0
    assert eng.pool.num_allocated == full + window
    eng.run_until_complete()
    names = [ev.name for ev in eng.profiler.events]
    spans = [n for n in names if n.startswith("serve.dispatch")]
    assert spans and all("experts_held=8" in n and "pages_by_kind" not in n
                         and "attn_pages" not in n for n in spans)
    assert eng.metrics.summary()["win_pool_occupancy_max"] \
        >= window / eng.pool.capacity
    rel = [n for n in names if n.startswith("serve.win_release")]
    assert rel and all("pages=4" in n for n in rel)
    assert eng.metrics.summary()["win_pages_released"] == 4 * len(rel)
    # a model of one table has none of it
    gpt = models.create("gpt2_tiny")
    assert refuse_windowed(gpt, prefix_cache=True) is None
    assert getattr(gpt, "page_groups", None) is None


def test_the_scopes_name_the_two_kinds_of_layer(model, weights):
    """``win_attn`` around the window layers' kernel, ``full_attn`` around
    the global layer's: what the per-layer metrics read."""
    pool = PagedKVPool(1, 2, 32, 40, 8, dtype=jnp.float32, groups=GROUPS)
    width = pool.table_width(64)
    text = jax.jit(model.apply_decode_paged).lower(
        weights[1], jnp.zeros((2,), jnp.int32), pool.pages_k, pool.pages_v,
        jnp.zeros((2, width), jnp.int32), jnp.zeros((2,), jnp.int32)) \
        .as_text(debug_info=True)
    assert text.count("h3/full_attn/paged_attn") > 0
    assert "h3/win_attn" not in text and "h0/full_attn" not in text
    for i in (0, 1, 2, 4):
        assert f"h{i}/win_attn/paged_attn" in text
    assert "h0/mlp" in text and "h1/moe_route" in text \
        and "h0/moe_route" not in text


# -- the refusals ---------------------------------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(spec="ngram"), "speculative decoding"),
    (dict(tp=2), "tensor parallelism"),
    (dict(sp=2), "sequence parallelism"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix sharing"),
    (dict(kv_dtype="int8"), "int8 pages")])
def test_the_engine_refuses_what_assumes_one_table(model, weights, kw, what):
    with pytest.raises(ValueError, match="two groups of page") as e:
        engine(model, weights[1], **kw)
    assert what in str(e.value) and str(e.value).count(".") <= 1
    assert "a window of 16" in str(e.value)


def test_one_refusal_function_for_the_three_kinds_of_state(model):
    msg = refuse_windowed(model, host_tier_bytes=1 << 20)
    assert "host KV tier" in msg and "two groups of page" in msg
    assert refuse_windowed(model) is None
    # the other two read as before
    eva = refuse_windowed(models.create("evabyte_tiny"), prefix_cache=True)
    assert "exact window of 32" in eva and "two groups" not in eva
    assert eva.endswith("a cached block would have to carry the summaries "
                        "of everything before it")
    lat = refuse_windowed(models.create("mistral_small4_tiny"), spec=True)
    assert "one latent row a token" in lat and "two groups" not in lat
    assert lat.endswith("is not held against the reference over latent "
                        "pages")


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
        serve.main(["--model", "trinity_large_tiny", *flags])
    assert e.value.code == 2 and what in err.getvalue()
    assert "random-weight" not in err.getvalue()


def test_the_published_sizes_of_the_served_model():
    """``trinity_large_ep8`` as the cell runs it: every width the source's."""
    m = models.create("trinity_large_ep8")
    assert (m.num_layers, m.d_model, m.num_heads, m.num_kv_heads,
            m.head_dim) == (5, 3072, 48, 8, 128)
    assert m.gated["layer_types"] == KINDS and m.gated["window"] == 4096
    assert [b.attn.window for b in m.blocks] == [4096] * 3 + [None, 4096]
    assert [b.attn.rope_theta for b in m.blocks] \
        == [10000.0] * 3 + [None, 10000.0]
    assert [b.moe is None for b in m.blocks] == [True] + [False] * 4
    assert [b.mlp_hidden for b in m.blocks] == [12288] + [3072] * 4
    assert m.experts["num_experts"] == 256 and len(m.experts["held"]) == 32
    assert m.page_groups == dict(window=4096, window_layers=4, full_layers=1)
    shapes = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), (1, 8))["params"])
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 4.32e9) < 0.01e9         # 8.64 GB of bf16
    assert shapes["h1"]["attn"]["qkvg_kernel"].shape == (3072, 14336)
    assert shapes["h1"]["moe"]["expert_bias"].dtype == jnp.float32
