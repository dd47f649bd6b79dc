"""EvaByte's block on the normal serving path (PR 28): EVA attention (an exact
window beside one learned summary a chunk), the pool of two kinds of page,
the kernel, the refusals, the spans and counters. Tiny sizes on the CPU (2
layers, 64 wide, 4 heads of 16, window 32, chunk 4), seeded weights, logits
held against ``chipbench/reference/evabyte.py``: the same module the
benchmark compares with, which imports nothing of the program."""
import contextlib
import io
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import evabyte as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.ops.pallas import eva_attention as eva
from tnn_tpu.ops.pallas import paged_attention as pa
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving.kv_pool import PagedKVPool
from tnn_tpu.serving.scheduler import Request, Scheduler

CFG = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
           num_attention_heads=4, vocab_size=320, window_size=32,
           chunk_size=4, num_pred_heads=2, max_position_embeddings=256,
           rope_theta=1e5, rms_norm_eps=1e-5)
W, C = CFG["window_size"], CFG["chunk_size"]
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The program in float32 against the float32 reference at precision
# "highest": what is left is the order of sums (XLA's default matmul
# precision on the CPU is float32) and the page round trip of K/V rows, none
# of which rounds. Logits of a model 64 wide are O(1); 2e-4 is a hundred
# float32 steps of them, and a wrong mask, position or summary moves them by
# tenths.
TOL = 2e-4


@pytest.fixture(scope="module")
def sz():
    return ref.sizes_of(CFG)


@pytest.fixture(scope="module")
def weights(sz):
    p = ref.make_params(sz, 28)
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(sz):
    m = models.create("evabyte_tiny", policy=F32)
    ref.check_program(m, sz, "evabyte_tiny")
    return m


@pytest.fixture(scope="module")
def forward(weights, sz):
    return ref.Forward(weights[0], sz, ref.forward_length(sz, 192))


def engine(model, params, **kw):
    cfg = dict(num_blocks=64, block_size=8, max_batch_size=4, chunk_size=16,
               prefix_cache=False, max_seq_len=192)
    cfg.update(kw)
    return InferenceEngine(model, params, **cfg)


_STEPS = {}


def _jitted(model):
    """The two forms of the step, compiled once a model and pool shape."""
    if id(model) not in _STEPS:
        _STEPS[id(model)] = (jax.jit(model.apply_paged),
                             jax.jit(model.apply_decode_paged))
    return _STEPS[id(model)]


def program_logits(model, params, ids, takes, block_size=8, decode_after=0):
    """Logits of ``ids`` through ``apply_paged`` over a pool of two kinds of
    page: the first tokens as prompt chunks of widths ``takes`` (cycled; a
    chunk is cut at a window's end, as the scheduler cuts it; padded to the
    window's width as the engine pads to a bucket), the last ``decode_after``
    one at a time through ``apply_decode_paged``."""
    chunk_fn, decode_fn = _jitted(model)
    pool = PagedKVPool(model.num_layers, model.num_kv_heads,
                       model.d_model // model.num_heads, 64, block_size,
                       dtype=jnp.float32, window=W, chunk=C)
    width = pool.table_width(256)
    req = Request(rid=0, prompt=np.asarray(ids, np.int32), max_new_tokens=1)
    out, pos, i = [], 0, 0
    n_prefill = len(ids) - decode_after
    while pos < len(ids):
        if pos < n_prefill:
            take = min(takes[i % len(takes)], n_prefill - pos,
                       pool.room_in_window(pos))
            i += 1
        else:
            take = 1
        need_e, need_s = pool.table_need(pos, take)
        req.block_table += pool.alloc(need_e - len(req.block_table))
        req.summary_table += pool.alloc(need_s - len(req.summary_table))
        table = np.zeros((1, width), np.int32)
        table[0, :len(req.block_table)] = req.block_table
        table[0, pool.exact_width:pool.exact_width
              + len(req.summary_table)] = req.summary_table
        pool.check_step_writes(table, [pos], [take])
        if pos < n_prefill:
            toks = np.zeros((1, W), np.int32)
            toks[0, :take] = ids[pos:pos + take]
            lg, pk, pv = chunk_fn(
                params, jnp.asarray(toks), pool.pages_k, pool.pages_v,
                jnp.asarray(table), jnp.asarray([pos]), jnp.asarray([take]))
            out.append(np.asarray(lg[0, :take]))
        else:
            lg, pk, pv = decode_fn(
                params, jnp.asarray(ids[pos:pos + 1], jnp.int32),
                pool.pages_k, pool.pages_v, jnp.asarray(table),
                jnp.asarray([pos]))
            out.append(np.asarray(lg))
        pool.update_pages(pk, pv)
        pos += take
        if pos % W == 0:
            pool.free(req.block_table)
            req.block_table = []
        pool.check_invariants([req.block_table], [pos], [req.summary_table])
    return np.concatenate(out)


# -- (1) prefill then decode through window ends, against the reference ------

@pytest.mark.parametrize("n_prompt,takes", [
    (37, [16]), (64, [16]), (70, [5, 11, 3]), (9, [16]), (33, [7]),
    (96, [13, 1, 16]), (45, [32]), (31, [2, 9])])
def test_prefill_then_decode_agrees_with_the_reference(
        model, weights, forward, n_prompt, takes):
    """Prompt chunks of widths that cross chunk ends (and stop at window
    ends), then 70 single-token steps through at least two window ends: every
    position's logits are the reference's full forward's."""
    ids = np.random.default_rng(n_prompt).integers(0, 320, n_prompt + 70)
    got = program_logits(model, weights[1], ids, takes, decode_after=70)
    want = forward.rows(ids, np.arange(len(ids)))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("block_size", [4, 8, 16, 32])
def test_any_page_size_that_divides_the_window(model, weights, forward,
                                               block_size):
    ids = np.random.default_rng(5).integers(0, 320, 100)
    got = program_logits(model, weights[1], ids, [16], block_size,
                         decode_after=20)
    assert np.abs(got - forward.rows(ids, np.arange(100))).max() < TOL


@pytest.mark.parametrize("prompts", [(37, 70, 9), (64, 32), (1, 95, 33, 12)])
def test_the_engine_serves_the_references_tokens(model, weights, forward,
                                                 prompts):
    """Scheduler -> pool -> step -> commit, several rows at once: chunked
    prefill, mixed steps (a row's prompt chunk beside other rows' decodes:
    the prompts differ in length, so rows reach decoding at different
    steps), decode through window ends. Greedy tokens are the reference's
    best at every position, or tie with it inside the tolerance."""
    rng = np.random.default_rng(len(prompts))
    ps = [rng.integers(0, 320, n).astype(np.int32) for n in prompts]
    eng = engine(model, weights[1])
    rids = [eng.submit(p, 60) for p in ps]
    out = eng.run_until_complete()
    eng.check_invariants()
    assert eng.stats()["decode_path"] == "paged"
    assert eng.pool.num_allocated == 0
    kinds = {str(k[0]) for k in eng._jit}
    assert {"mixed", "pdecode"} <= kinds
    for rid, p in zip(rids, ps):
        ids = list(p) + out[rid]
        lg = forward.rows(ids, np.arange(len(p) - 1, len(ids) - 1))
        gap = lg.max(-1) - lg[np.arange(len(out[rid])), out[rid]]
        assert len(out[rid]) == 60 and gap.max() < TOL


@pytest.mark.parametrize("ramp", [8, 1])
def test_the_overlapped_loop_serves_the_same_tokens(model, weights,
                                                    monkeypatch, ramp):
    """``tnn-serve``'s default loop (steps dispatched before the last one is
    fetched, never over a window's end; ``ramp`` 1: a step deeper with every
    adoption, so a dozen are queued between two windows' ends) against the
    synchronous one, with every packed step held to the one-writer invariant
    and the pool's bookkeeping checked at every mutation (TNN_POOL_DEBUG)."""
    from tnn_tpu.serving import engine as engine_lib

    monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", ramp)
    monkeypatch.setattr(engine_lib, "SPECULATE_AHEAD_S", 3600.0)
    monkeypatch.setenv("TNN_POOL_DEBUG", "1")
    rng = np.random.default_rng(11)
    ps = [rng.integers(0, 320, n).astype(np.int32) for n in (29, 50, 64)]

    def run(overlap):
        eng = engine(model, weights[1], overlap=overlap)
        assert eng.pool.debug
        rids = [eng.submit(p, 70) for p in ps]
        out = eng.run_until_complete()
        eng.check_invariants()
        return eng, [out[r] for r in rids]

    sync, want = run(False)
    over, got = run(True)
    assert got == want
    assert over.metrics.summary()["eva_windows_rolled"] \
        == sync.metrics.summary()["eva_windows_rolled"] >= 6
    assert over.metrics.summary()["eva_window_fill_mean"] == pytest.approx(
        sync.metrics.summary()["eva_window_fill_mean"])


def test_a_mixed_step_one_rows_chunk_beside_other_rows_decodes(
        model, weights, forward):
    """Two requests decode; a third arrives and prefills beside them."""
    rng = np.random.default_rng(2)
    a, b, c = (rng.integers(0, 320, n).astype(np.int32) for n in (20, 41, 50))
    eng = engine(model, weights[1])
    ra, rb = eng.submit(a, 40), eng.submit(b, 40)
    for _ in range(6):
        eng.step()
    assert all(r.cache_len >= r.prefill_len for r in eng.scheduler.running)
    rc = eng.submit(c, 30)
    out = eng.run_until_complete()
    for rid, p in ((ra, a), (rb, b), (rc, c)):
        ids = list(p) + out[rid]
        lg = forward.rows(ids, np.arange(len(p) - 1, len(ids) - 1))
        assert (lg.max(-1) - lg[np.arange(len(out[rid])), out[rid]]
                ).max() < TOL


# -- (3) no longer than the window: plain causal attention -------------------

@pytest.mark.parametrize("n", [1, 17, 32])
def test_within_one_window_it_is_plain_causal_attention(model, weights, n):
    """A sequence no longer than the window through the pool gives the
    logits of the block's plain forward (``model.apply``: rotary, causal
    softmax from the code that was there), which reads no page, phi or mu."""
    ids = np.random.default_rng(n).integers(0, 320, n)
    plain, _ = model.apply({"params": weights[1], "state": {}},
                           jnp.asarray(ids)[None])
    got = program_logits(model, weights[1], ids, [16])
    assert np.abs(got - np.asarray(plain[0])).max() < TOL


def test_past_one_window_the_plain_forward_refuses(model, weights):
    with pytest.raises(NotImplementedError, match="longer than the window"):
        model.apply({"params": weights[1], "state": {}},
                    jnp.zeros((1, W + 1), jnp.int32))


# -- (4) the kernel in interpret mode against the jax.numpy path -------------

def _pool_case(seed, b, hkv, g, dh, bs, n_exact, n_sum, dtype):
    rng = np.random.default_rng(seed)
    n = 1 + b * (n_exact + n_sum)
    pk = jnp.asarray(rng.normal(size=(2, n, hkv, bs, dh)), dtype)
    pv = jnp.asarray(rng.normal(size=(2, n, hkv, bs, dh)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(
        b, n_exact + n_sum), jnp.int32)
    return rng, pk, pv, tables


def _kernel_against_numpy(qw, q_lens, exact_lens, sum_lens, positions,
                          n_exact=4, n_sum=3):
    """Both paths at heads of 128 over pages of 8 with a group of
    ``positions`` key positions; returns the kernel's output."""
    b = len(q_lens)
    rng, pk, pv, tables = _pool_case(qw, b, 4, 1, 128, 8, n_exact, n_sum,
                                     jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, qw, 4, 128)), jnp.float32)
    args = (q, pk, pv, tables, jnp.asarray(exact_lens), jnp.asarray(sum_lens))
    kw = dict(n_exact=n_exact, q_lens=jnp.asarray(q_lens), layer=1)
    want = eva.eva_attention(*args, backend="xla", **kw)
    with mock.patch.object(eva, "GROUP_POSITIONS", positions):
        pages, _ = pa.fetch_group(bs=8, dh=128, hkv=4, qg=qw,
                                  page_dtype=jnp.float32, nb=n_exact + n_sum,
                                  positions=positions)
        assert pages == max(1, min(positions // 8, n_exact + n_sum))
        got = eva.eva_attention(*args, backend="pallas", interpret=True, **kw)
    # the same float32 sums in another order (a running softmax group by
    # group)
    assert float(jnp.abs(got - want).max()) < 2e-5
    dead = np.asarray(q_lens)[:, None] <= np.arange(qw)[None]
    assert not np.asarray(got)[dead].any()     # padding outputs exactly 0
    return np.asarray(got)


@pytest.mark.kernel
@pytest.mark.parametrize("qw,q_lens,exact_lens,sum_lens", [
    (1, [1, 1, 1], [5, 32, 1], [0, 17, 24]),
    (8, [8, 3, 0], [8, 30, 0], [8, 0, 3]),
    (16, [16, 9, 1], [20, 32, 1], [0, 24, 5])])
@pytest.mark.parametrize("positions", [8, 24, 32])
def test_kernel_matches_the_numpy_path_at_head_128(qw, q_lens, exact_lens,
                                                   sum_lens, positions):
    """The group the kernel derives (``fetch_group`` at ``GROUP_POSITIONS``)
    of 1, 3 and 4 pages over a table of 4 exact and 3 summary entries: at 3
    the second group straddles ``n_exact``, at 4 the table is padded by one
    entry; in the decode form, in a chunk, in a chunk wider than a page."""
    _kernel_against_numpy(qw, q_lens, exact_lens, sum_lens, positions)


@pytest.mark.kernel
@pytest.mark.parametrize("qw", [1, 8])
@pytest.mark.parametrize("positions,n_exact", [(24, 4), (16, 3), (40, 6)])
def test_a_group_may_straddle_n_exact(qw, positions, n_exact):
    """``n_exact`` no multiple of ``pages``: one group holds the exact
    segment's last pages and the summaries' first, each slot under its own
    segment's rule: rows with both segments live in that group, with the
    exact pages of it dead, with the summaries of it dead, with neither."""
    width = n_exact * 8
    _kernel_against_numpy(
        qw, [qw, qw, qw, qw], [width, qw, width, qw], [19, 24, 0, 0],
        positions, n_exact=n_exact)


@pytest.mark.kernel
@pytest.mark.parametrize("positions", [8, 24, 128])
def test_rows_at_the_edges_of_the_two_segments(positions):
    """A row with no summary, a row of ONE live position (a window's first,
    with summaries and without), a row whose window and summaries are full,
    and a dead row, whose output is exactly 0, in one decode launch."""
    got = _kernel_against_numpy(1, [1, 1, 1, 1, 0], [17, 1, 1, 32, 0],
                                [0, 0, 9, 24, 0], positions)
    assert not got[4].any() and got[:4].any(axis=(1, 2, 3)).all()


@pytest.mark.kernel
def test_a_row_of_one_live_position_returns_its_value():
    """One exact position and no summary: the softmax over one key is 1, so
    the output is that position's value row, whatever the group."""
    rng, pk, pv, tables = _pool_case(3, 1, 4, 1, 128, 8, 4, 3, jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 4, 128)), jnp.float32)
    got = eva.eva_attention(q, pk, pv, tables, jnp.asarray([1]),
                            jnp.asarray([0]), n_exact=4, layer=1,
                            backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(pv[1, tables[0, 0], :, 0]),
                               rtol=1e-6)


def test_dead_entries_repeat_what_the_pipeline_fetched_last():
    """``_fetch_table``: live entries as they are; a dead page repeats the
    last live page of its segment, and a group with no live page repeats the
    group before it slot for slot (the same block indices: no DMA)."""
    tables = jnp.arange(100, 107, dtype=jnp.int32)[None].repeat(3, 0)
    got = np.asarray(eva._fetch_table(
        tables, jnp.asarray([9, 32, 0]), jnp.asarray([0, 17, 0]), 4, 8, 2))
    # row 0: exact pages 0-1 live; group 1 (entries 2, 3) dead: repeats
    # group 0; no summary: groups 2 and 3 (entries 4-7) dead too
    assert got[0].tolist() == [100, 101] * 4
    # row 1: all 4 exact pages, 3 summary pages (17 rows of 8); the padding
    # entry 7 repeats the last live summary page
    assert got[1].tolist() == [100, 101, 102, 103, 104, 105, 106, 106]
    # a dead row stays at entry 0 of each segment's clamp, group 0 repeated
    assert got[2].tolist() == [100, 100] * 4
    one = np.asarray(eva._fetch_table(
        tables, jnp.asarray([9, 32, 0]), jnp.asarray([0, 17, 0]), 4, 8, 1))
    assert one[0].tolist() == [100, 101, 101, 101, 101, 101, 101]


def test_fetch_group_at_the_published_shapes():
    """``fetch_group``'s answers where the benchmark's cells run it: a later
    change of the budget or of ``group_vmem_bytes`` shows here. EvaByte (32
    heads of 128 over pages of 128, a table of 16 + 16): the decode form and
    a chunk of 256; GPT-2 large (10 page rows of two heads of 64, pages of
    16); Trinity's global and window layers (8 KV heads of 128, 6 queries a
    head); Mistral's latent rows of 384 lanes under 32 heads."""
    from tnn_tpu.nn.attention import GATED_GROUP_POSITIONS
    from tnn_tpu.ops.pallas import mla_attention as mla

    bf16 = jnp.bfloat16
    evabyte = dict(bs=128, dh=128, hkv=32, page_dtype=bf16, nb=32,
                   positions=eva.GROUP_POSITIONS)
    assert pa.fetch_group(qg=1, **evabyte) == (1, 32)
    assert pa.fetch_group(qg=256, **evabyte) == (1, 8)
    gpt2 = dict(bs=16, dh=128, hkv=10, page_dtype=bf16, nb=64)
    assert pa.fetch_group(qg=2, **gpt2) == (8, 10)
    assert pa.fetch_group(qg=2 * 64, **gpt2) == (8, 10)
    trinity = dict(bs=128, dh=128, hkv=8, page_dtype=bf16,
                   positions=GATED_GROUP_POSITIONS)
    assert pa.fetch_group(qg=6, nb=289, **trinity) == (4, 8)
    assert pa.fetch_group(qg=6, nb=pa.window_walk(4096, 1, 128, 34),
                          **trinity) == (4, 8)
    mistral = dict(bs=128, dh=384, hkv=1, page_dtype=bf16, nb=256,
                   positions=mla.GROUP_POSITIONS)
    assert pa.fetch_group(qg=32, **mistral) == (8, 1)
    # a chunk of 64: tiles of 16 tokens x 32 heads, and half the pages
    assert pa.fetch_group(qg=mla.query_tile(64, 32) * 32, **mistral) == (4, 1)


@pytest.mark.kernel
def test_kernel_decode_form_grouped_heads_bf16():
    rng, pk, pv, tables = _pool_case(9, 2, 2, 2, 128, 16, 2, 2, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(2, 4, 128)), jnp.bfloat16)
    args = (q, pk, pv, tables, jnp.asarray([7, 32]), jnp.asarray([0, 20]))
    want = eva.eva_attention(*args, n_exact=2, backend="xla")
    got = eva.eva_attention(*args, n_exact=2, backend="pallas",
                            interpret=True)
    assert got.shape == q.shape
    # bf16 outputs: one step of bf16 at O(1) is 0.008
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 0.02


def test_summaries_are_the_references(weights, sz):
    """``write_summaries`` on rows in pages against the reference's formula."""
    rng = np.random.default_rng(4)
    h, dh, bs = 4, 16, 8
    k = rng.normal(size=(h, W, dh)).astype(np.float32)
    v = rng.normal(size=(h, W, dh)).astype(np.float32)
    phi, mu = (rng.normal(size=(h, dh)).astype(np.float32) for _ in range(2))
    ks, vs = ref.summaries(jnp.asarray(k), jnp.asarray(v), jnp.asarray(phi),
                           jnp.asarray(mu), C)
    pages_k = np.zeros((1, 8, h, bs, dh), np.float32)
    pages_v = np.zeros_like(pages_k)
    for j in range(W // bs):                 # exact pages 1..4, summary 5
        pages_k[0, 1 + j] = k[:, j * bs:(j + 1) * bs]
        pages_v[0, 1 + j] = v[:, j * bs:(j + 1) * bs]
    tables = jnp.asarray([[1, 2, 3, 4, 5, 0]], jnp.int32)
    # a step that wrote positions 6..20 of the window: chunks 1..4 complete
    pk, pv = eva.write_summaries(
        jnp.asarray(pages_k), jnp.asarray(pages_v), tables,
        jnp.asarray([6]), jnp.asarray([15]), jnp.asarray(phi),
        jnp.asarray(mu), n_exact=4, window=W, chunk=C, layer=0, qw=16)
    assert np.abs(np.asarray(pk[0, 5])[:, 1:5] - np.asarray(ks)[:, 1:5]
                  ).max() < 1e-5
    assert np.abs(np.asarray(pv[0, 5])[:, 1:5] - np.asarray(vs)[:, 1:5]
                  ).max() < 1e-5
    assert not np.asarray(pk[0, 5])[:, [0, 5, 6, 7]].any()   # others untouched


# -- (5) the pool of two kinds of page ---------------------------------------

def windowed_pool(num_blocks=32, bs=8):
    return PagedKVPool(1, 2, 4, num_blocks, bs, window=W, chunk=C)


@pytest.mark.parametrize("cache_len,new,want", [
    (0, 1, (1, 0)), (0, 16, (2, 1)), (31, 1, (4, 1)), (32, 0, (0, 1)),
    (32, 1, (1, 1)), (60, 4, (4, 2)), (95, 1, (4, 3)), (96, 8, (1, 4))])
def test_table_need_of_both_kinds(cache_len, new, want):
    assert windowed_pool().table_need(cache_len, new) == want


def test_gpt2s_pool_is_the_case_of_no_window():
    pool = PagedKVPool(1, 2, 4, 32, 8)
    assert pool.window is None and pool.exact_width == 0
    assert pool.table_need(30, 3) == (5, 0) == (pool.blocks_for(33), 0)
    assert pool.lifetime_blocks(100) == pool.table_width(100) == 13
    assert pool.room_in_window(10 ** 9) > 10 ** 9
    assert pool.token_capacity == 31 * 8


def test_lifetime_width_and_room():
    pool = windowed_pool()
    assert pool.exact_width == 4
    assert pool.lifetime_blocks(20) == 3 + 1        # 20 exact rows, 5 chunks
    assert pool.lifetime_blocks(200) == 4 + 7       # a window, 50 summaries
    assert pool.table_width(200) == 4 + 7
    assert [pool.room_in_window(n) for n in (0, 31, 32, 40)] == [32, 1, 32, 24]
    assert pool.token_capacity == (31 - 4) * 8 * C


@pytest.mark.parametrize("kw,why", [
    (dict(window=30, chunk=5), "multiple of block_size"),
    (dict(window=32, chunk=5), "multiple of block_size"),
    (dict(window=32), "come together"),
    (dict(window=32, chunk=4, kv_dtype="int8"), "nor int8"),
    (dict(window=32, chunk=4, sp=2), "nor int8")])
def test_pool_refuses_a_window_it_cannot_page(kw, why):
    with pytest.raises(ValueError, match=why):
        PagedKVPool(1, 2, 4, 32, 8, **kw)


def test_exact_pages_are_released_at_a_windows_end_and_rows_counted(
        model, weights):
    eng = engine(model, weights[1])
    rid = eng.submit(np.arange(30, dtype=np.int32), 40)
    req = eng.requests[rid]
    seen = []
    while eng.has_work:
        eng.step()
        eng.check_invariants()
        if req.state.value == "running":
            seen.append((req.cache_len, len(req.block_table),
                         len(req.summary_table)))
    by_len = {n: (e, s) for n, e, s in seen}
    assert by_len[30] == (4, 1)         # 30 exact rows, 7 summaries
    assert by_len[32] == (0, 1)         # the window ended: no exact page
    assert by_len[33] == (1, 1)         # one row of the new window; 8 rows
    assert by_len[36] == (1, 2)         # the ninth summary: a second page
    assert by_len[64] == (0, 2)
    assert eng.metrics.summary()["eva_windows_rolled"] == 2
    assert eng.pool.num_allocated == 0


def test_check_invariants_knows_both_tables():
    pool = windowed_pool()
    exact, summ = pool.alloc(2), pool.alloc(1)
    pool.check_invariants([exact], [12], [summ])
    with pytest.raises(ValueError, match="hold 2 exact and 0 summary"):
        pool.check_invariants([exact], [12], [[]])      # summary page lost
    with pytest.raises(ValueError, match="leaked"):
        pool.check_invariants([exact], [12], [pool.alloc(1)])
    with pytest.raises(ValueError, match="summary blocks"):
        pool.check_invariants([exact], [40], [summ])    # 10 chunks: 2 pages
    with pytest.raises(ValueError, match="exact"):
        pool.check_invariants([exact + pool.alloc(2)], [33], [summ])


def test_check_step_writes_knows_both_kinds():
    pool = windowed_pool()
    a, b = pool.alloc(5), pool.alloc(5)
    tables = np.asarray([a[:4] + a[4:] + [0], b[:4] + b[4:] + [0]])
    pool.check_step_writes(tables, [40, 0], [8, 16])
    with pytest.raises(ValueError, match="across the end of a window"):
        pool.check_step_writes(tables, [30, 0], [4, 1])
    shared = tables.copy()
    shared[1, 4] = shared[0, 4]                     # one summary page, twice
    with pytest.raises(ValueError, match="written by rows"):
        pool.check_step_writes(shared, [12, 0], [4, 4])
    pool.check_step_writes(shared, [12, 0], [3, 3])     # no chunk completed


def test_a_preempted_request_reproduces_its_tokens(model, weights):
    rng = np.random.default_rng(8)
    ps = [rng.integers(0, 320, n).astype(np.int32) for n in (40, 45)]
    base = engine(model, weights[1])
    want = [base.submit(p, 50) for p in ps]
    want = [base.run_until_complete()[r] for r in want]
    eng = engine(model, weights[1])
    rids = [eng.submit(p, 50) for p in ps]
    for _ in range(40):
        eng.step()
    victim = eng.scheduler.preempt_victim()
    assert victim.num_generated > 20 and victim.summary_table
    eng._preempt(victim)
    assert not victim.block_table and not victim.summary_table
    eng.check_invariants()
    out = eng.run_until_complete()
    assert [out[r] for r in rids] == want
    assert eng.metrics.summary()["preemptions"] == 1
    assert eng.pool.num_allocated == 0


def test_admission_holds_a_request_to_its_last_token(model, weights):
    """A windowed pool admits what fits to its last token beside what the
    running requests may still take: the second request waits though its
    first chunk would fit, and nothing is ever preempted."""
    eng = engine(model, weights[1], num_blocks=17)     # 16 blocks to give
    need = eng.pool.lifetime_blocks(30 + 120)          # 4 + 5
    assert need == 9
    a = eng.submit(np.arange(30, dtype=np.int32), 120)
    b = eng.submit(np.arange(30, dtype=np.int32) + 1, 120)
    eng.step()
    assert [r.rid for r in eng.scheduler.running] == [a]
    assert eng.scheduler.queue_depth == 1
    assert eng.pool.num_allocatable >= 9        # "fits now" would admit it
    out = eng.run_until_complete()
    assert len(out[a]) == len(out[b]) == 120
    assert eng.metrics.summary()["preemptions"] == 0
    small = engine(model, weights[1], num_blocks=9)     # 8 blocks to give
    assert small.max_seq_len == small.pool.token_capacity == 4 * 8 * C
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        small.submit(np.arange(30, dtype=np.int32), 120)


def test_scheduler_ends_a_grant_at_a_windows_end():
    pool = windowed_pool(num_blocks=64)
    sch = Scheduler(max_batch_size=2, token_budget=64, chunk_size=20)
    req = Request(rid=0, prompt=np.zeros(70, np.int32), max_new_tokens=4)
    sch.submit(req)
    takes = []
    for _ in range(6):
        plan = sch.schedule(pool)
        for r in plan.prefills:
            r.cache_len = 0
            sch.admit(r)
        take = plan.chunks.get(0)
        if not take:
            break
        takes.append(take)
        req.cache_len += take
    assert takes == [20, 12, 20, 12, 6]


# -- (6) the refusals ---------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(spec="ngram"), "speculative decoding"),
    (dict(tp=2), "tensor parallelism"),
    (dict(sp=2), "sequence parallelism"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix sharing"),
    (dict(kv_dtype="int8"), "int8 pages")])
def test_the_engine_refuses_what_assumes_kv_blocks(model, weights, kw, what):
    with pytest.raises(ValueError, match="exact window of 32") as e:
        engine(model, weights[1], **kw)
    assert what in str(e.value) and str(e.value).count(".") <= 1


def test_the_host_tier_has_its_own_sentence(model):
    msg = refuse_windowed(model, host_tier_bytes=1 << 20)
    assert "host KV tier" in msg
    assert refuse_windowed(model) is None
    assert refuse_windowed(models.create("gpt2_tiny"), prefix_cache=True,
                           spec=True, tp=2, kv_dtype="int8") is None


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
        serve.main(["--model", "evabyte_tiny", *flags])
    assert e.value.code == 2 and what in err.getvalue()
    assert "random-weight" not in err.getvalue()


# -- (7)/(9) the block, the spans and the counters ----------------------------

def test_llama_gains_the_paged_path(weights):
    """``models/llama.py`` takes the same block: a plain Llama (no window)
    now serves through scheduler -> pool -> ``tnn_paged_attention``."""
    from tnn_tpu.models.llama import Llama

    m = Llama(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
              vocab_size=97, max_len=64, policy=F32)
    params = m.init(jax.random.PRNGKey(1), (1, 8))["params"]
    eng = InferenceEngine(m, params, num_blocks=32, block_size=4,
                          max_batch_size=2, max_seq_len=48)
    assert eng.stats()["decode_path"] == "paged"
    p = np.arange(11, dtype=np.int32) % 97
    rid = eng.submit(p, 12)
    out = eng.run_until_complete()[rid]
    ids = jnp.asarray(list(p) + out)[None]
    full, _ = m.apply({"params": params, "state": {}}, ids)
    assert out == [int(t) for t in np.asarray(
        full[0, len(p) - 1:-1].argmax(-1))]


def test_eva_window_fill_mean_on_a_hand_counted_run(model, weights):
    """One request, prompt 30 in chunks of 16, 5 tokens out. Steps: chunk
    0..15 (fill 16/32), chunk 16..29 (30/32), then decode steps at positions
    30, 31 (fills 31/32, 32/32), the window ends, positions 32, 33 (1/32,
    2/32)."""
    eng = engine(model, weights[1])
    eng.submit(np.arange(30, dtype=np.int32), 5)
    eng.run_until_complete()
    s = eng.metrics.summary()
    fills = [16, 30, 31, 32, 1, 2]
    assert s["eva_window_fill_mean"] == pytest.approx(
        sum(fills) / 32 / len(fills))
    assert s["eva_windows_rolled"] == 1
    # at the last step the request held 33 // 4 = 8 summary rows
    assert s["eva_summary_rows_max"] == pytest.approx(
        8 / (eng.pool.capacity * 8))
    assert "eva_window_fill_mean" not in InferenceEngine(
        models.create("gpt2_tiny"), {}, num_blocks=4).metrics.summary()


def test_attn_fetch_fill_mean_counts_both_segments(model, weights):
    """``summary()["attn_fetch_fill_mean"]`` of a windowed engine: a row's
    live pages of BOTH segments (the exact pages by its window-relative
    length, the summary pages by the 8 rows a finished window leaves) over
    the page slots of the groups ``tnn_eva_attention`` fetches for them,
    with the group the kernel's own launch derives. Groups of 3 pages here
    (24 positions), so that one straddles the 4 exact entries."""
    with mock.patch.object(eva, "GROUP_POSITIONS", 24):
        eng = engine(model, weights[1])
        assert "attn_fetch_fill_mean" not in eng.metrics.summary()
        assert eng.pool.exact_width == 4 and eng.blocks_per_seq == 10
        assert eng._attn_group(1) == pa.fetch_group(
            bs=8, dh=16, hkv=4, qg=1, page_dtype=eng.pool.dtype, nb=10,
            positions=24) == (3, 4)
        attrs = eng._program_attrs(None, 1)
        assert (attrs["attn_pages"], attrs["attn_heads"]) == (3, 4)
        # rows that end before positions 1, 32, 33, 100 and 96 hold 1, 4, 1,
        # 1 and 4 exact pages and 0, 0, 1, 3 and 2 summary pages, in 1, 2,
        # 2, 3 and 2 groups of 3 slots (at 96 the group of entries 3-5 holds
        # the window's last page AND both summary pages: it counts once);
        # the sixth entry is not a live row
        eng._observe_attention([None] * 5,
                               np.array([1, 32, 33, 100, 96, 7]), 1)
        want = (1 / 3 + 4 / 6 + 2 / 6 + 4 / 9 + 6 / 6) / 5
        assert eng.metrics.summary()["attn_fetch_fill_mean"] \
            == pytest.approx(want)
        # a step whose rows hold nothing yet adds nothing
        eng._observe_attention([None] * 2, np.array([0, 0]), 16)
        assert eng.metrics.summary()["attn_fetch_fill_mean"] \
            == pytest.approx(want)
    # and the engine feeds it, at the group the module states: the whole
    # table of 10 entries is one group of the tiny model's pages of 8.
    # Prompt 30 in chunks of 16, 5 tokens out: steps that end before 16, 30,
    # 31, 32 (2, 4, 4, 4 exact pages), then 33, 34 in the next window (1
    # exact page, 8 summary rows in 1 page)
    eng = engine(model, weights[1])
    assert eng._attn_group(1) == (10, 4)
    eng.submit(np.arange(30, dtype=np.int32), 5)
    eng.run_until_complete()
    assert eng.metrics.attn_fetch_row_steps == 6
    assert eng.metrics.summary()["attn_fetch_fill_mean"] == pytest.approx(
        (2 + 4 + 4 + 4 + 2 + 2) / 10 / 6)


def test_scopes_and_kernel_name_are_in_the_compiled_step(model, weights):
    """The device profile finds the EVA layer by these: scopes ``eva_attn``
    and ``eva_summarise`` in op paths, the kernel by ``tnn_eva_attention``."""
    pool = PagedKVPool(2, 4, 16, 16, 8, dtype=jnp.float32, window=W, chunk=C)
    tables = jnp.zeros((2, pool.table_width(64)), jnp.int32)
    def lowered():      # a new function a call: no trace is found again
        return jax.jit(lambda *a: model.apply_decode_paged(*a)).lower(
            weights[1], jnp.zeros((2,), jnp.int32), pool.pages_k,
            pool.pages_v, tables, jnp.asarray([3, 40])
            ).as_text(debug_info=True)

    text = lowered()
    for scope in ("h0/eva_attn", "h1/eva_summarise", "kv_write", "attn_qkv",
                  "mlp", "lm_head", "embed", "ln_f"):
        assert scope in text, scope
    assert "tnn_kv_row_write" not in text       # off the chip: whole pages
    # on the chip both writes are the row-tile kernel, each under its scope
    # (the tiny pages' rows do not fill the 128 lanes the kernel asks for
    # there: the choice is made for it, the names are what is held)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(pa, "_kernel_writes", lambda pages: True), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "1"}):
        text = lowered()
    for i in range(2):
        for scope in ("kv_write", "eva_summarise"):
            assert f"h{i}/{scope}/jit(_write_rows_pallas)" in text, (i, scope)
    assert '"tnn_kv_row_write/' in text
    assert "eva_attn/tnn_eva_attention/" in text
    call = str(jax.make_jaxpr(lambda *a: eva.eva_attention(
        *a, n_exact=4, backend="pallas", interpret=False))(
        jnp.zeros((2, 4, 128), jnp.bfloat16),
        jnp.zeros((1, 8, 4, 8, 128), jnp.bfloat16),
        jnp.zeros((1, 8, 4, 8, 128), jnp.bfloat16),
        jnp.zeros((2, 6), jnp.int32), jnp.ones((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32)))
    assert "tnn_eva_attention" in call


def test_a_windows_end_is_a_host_span(model, weights):
    from tnn_tpu.profiling.profiler import Profiler

    prof = Profiler(source="t")
    eng = engine(model, weights[1], profiler=prof, trace=True)
    eng.submit(np.arange(30, dtype=np.int32), 5)
    eng.run_until_complete()
    names = [e.name for e in prof.events]
    assert sum(n.startswith("serve.eva_roll") for n in names) == 1
    assert any("at=32" in n for n in names if n.startswith("serve.eva_roll"))
