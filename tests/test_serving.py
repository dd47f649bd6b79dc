"""Serving engine tests: paged KV pool bookkeeping, scheduler policy, and
end-to-end continuous batching with token-for-token parity against
models.gpt2.generate (the offline single-sequence reference path).

Parity methodology: the engine attends the pool's pages through block
tables of a fixed width (blocks_per_seq * block_size positions) and the
reference, which assembles a contiguous cache, is run with ``max_len`` equal
to that width; greedy outputs must match exactly. Feature suites run on two
model families (``FAMILIES``): the GPT-2 block and the rotary / RMSNorm /
gated / grouped-head block the EvaByte cell runs.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tnn_tpu.serving import (TERMINAL_STATES, AdmissionRejected, Autoscaler,
                             BreakerState, CircuitBreaker, EngineCrash,
                             EngineSupervisor, FaultPlan, HostKVTier,
                             InferenceEngine, PagedKVPool, PoolExhausted,
                             PrefixCache, Request, RequestState, Router,
                             Scheduler, ShuttingDown, SupervisorState)
from tnn_tpu.ops.pallas.paged_attention import scatter_kv_chunk


# -- pool bookkeeping ---------------------------------------------------------


class TestPagedKVPool:
    def _pool(self, **kw):
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("head_dim", 4)
        kw.setdefault("num_blocks", 8)
        kw.setdefault("block_size", 4)
        return PagedKVPool(**kw)

    def test_alloc_free_roundtrip(self):
        pool = self._pool()
        assert pool.capacity == 7 and pool.num_free == 7
        blocks = pool.alloc(3)
        assert len(blocks) == 3 and PagedKVPool.SCRATCH not in blocks
        assert pool.num_allocated == 3
        pool.free(blocks)
        assert pool.num_free == 7 and pool.num_allocated == 0

    def test_exhaustion_raises(self):
        pool = self._pool()
        pool.alloc(7)
        assert not pool.can_alloc(1)
        with pytest.raises(PoolExhausted):
            pool.alloc(1)

    def test_double_free_raises(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(KeyError):
            pool.free(blocks)

    def test_refcount_fork(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        pool.fork(blocks)
        pool.free(blocks)           # one ref left
        assert pool.num_allocated == 2
        pool.free(blocks)           # last ref
        assert pool.num_allocated == 0

    def test_blocks_for(self):
        pool = self._pool(block_size=4)
        assert pool.blocks_for(0) == 1   # even empty sequences hold a block
        assert pool.blocks_for(4) == 1
        assert pool.blocks_for(5) == 2

    def test_write_after_fragmentation(self):
        """Logical order must follow the block TABLE, not block-id order —
        tables acquired after frees interleave arbitrarily in the pool. The
        system's own write (``scatter_kv_chunk``) lands position p in page
        ``table[p // bs]``, slot ``p % bs``."""
        pool = self._pool(num_layers=1, num_kv_heads=1, head_dim=2,
                          num_blocks=8, block_size=2)
        a = pool.alloc(2)
        b = pool.alloc(2)
        pool.free(a)
        c = pool.alloc(3)  # reuses a's blocks (LIFO) + one fresh: fragmented
        assert set(a) & set(c), "expected block reuse to fragment the table"
        seq = jnp.broadcast_to(
            jnp.arange(6, dtype=jnp.float32)[None, :, None, None],
            (1, 6, 1, 2))                                   # (B, Q, H, Dh)
        table = jnp.asarray([pool.padded_table(c, 4)])
        zero, six = jnp.asarray([0]), jnp.asarray([6])
        pool.update_pages(
            scatter_kv_chunk(pool.pages_k, table, zero, seq, six, layer=0),
            scatter_kv_chunk(pool.pages_v, table, zero, -seq, six, layer=0))
        pk = np.asarray(pool.pages_k)[0, :, 0, :, 0]        # (N, bs)
        got = np.concatenate([pk[blk] for blk in c])
        np.testing.assert_array_equal(got, np.arange(6, dtype=np.float32))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(pool.pages_v)[0, blk, 0, :, 0]
                            for blk in c]), -got)
        # nobody else's page was touched
        for blk in b:
            assert not pk[blk].any()

    def test_write_lands_in_right_slot(self):
        pool = self._pool(num_layers=1, num_kv_heads=1, head_dim=2,
                          num_blocks=8, block_size=4)
        blocks = pool.alloc(2)
        tables = jnp.asarray([pool.padded_table(blocks, 2)])
        # position 5 = second block, slot 1
        rows = jnp.full((1, 1, 1, 2), 7.0)                  # (B, Q, H, Dh)
        pages = scatter_kv_chunk(pool.pages_k, tables, jnp.asarray([5]),
                                 rows, jnp.asarray([1]), layer=0)
        got = np.asarray(pages)[0, blocks[1], 0]            # (bs, Dh)
        np.testing.assert_array_equal(got[1], [7.0, 7.0])
        assert not got[[0, 2, 3]].any()
        assert not np.asarray(pages)[0, blocks[0]].any()


# -- scheduler policy ---------------------------------------------------------


def _req(rid, plen, max_new=4):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new_tokens=max_new)


class TestScheduler:
    def _pool(self):
        return PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=2,
                           num_blocks=9, block_size=4)

    def test_fcfs_admission(self):
        sched = Scheduler(max_batch_size=2, token_budget=100)
        pool = self._pool()
        for i in range(3):
            sched.submit(_req(i, 4))
        plan = sched.schedule(pool)
        assert [r.rid for r in plan.prefills] == [0, 1]  # batch cap
        for r in plan.prefills:
            r.block_table = pool.alloc(1)
            sched.admit(r)
        assert sched.schedule(pool).prefills == []       # batch full

    def test_head_of_line_blocking(self):
        """A queue head that does not fit must block later (fitting) requests
        — out-of-order admission would starve big prompts forever."""
        sched = Scheduler(max_batch_size=4, token_budget=100)
        pool = self._pool()
        pool.alloc(6)                       # only 2 blocks (8 tokens) free
        sched.submit(_req(0, 12))           # needs 3 blocks: blocked
        sched.submit(_req(1, 4))            # would fit, but is behind 0
        assert sched.schedule(pool).prefills == []

    def test_token_budget_defers_prefill(self):
        sched = Scheduler(max_batch_size=4, token_budget=10)
        pool = self._pool()
        for i in range(3):
            sched.submit(_req(i, 8))
        plan = sched.schedule(pool)
        # 8 of the budget go to the head, the 2 left to the next one's first
        # chunk; the third finds no budget and waits
        assert [r.rid for r in plan.prefills] == [0, 1]
        assert plan.chunks == {0: 8, 1: 2}
        # with no budget at all a request still starts when it is the ONLY
        # work
        sched2 = Scheduler(max_batch_size=4, token_budget=0)
        sched2.submit(_req(9, 8))
        plan2 = sched2.schedule(pool)
        assert [r.rid for r in plan2.prefills] == [9]
        assert plan2.chunks == {9: 1}

    def test_requeue_goes_to_front(self):
        sched = Scheduler(max_batch_size=4, token_budget=100)
        a, b = _req(0, 4), _req(1, 4)
        sched.submit(a)
        sched.admit(sched.waiting.popleft())
        sched.submit(b)
        victim = sched.preempt_victim()
        assert victim is a
        sched.requeue(victim)
        assert [r.rid for r in sched.waiting] == [0, 1]
        assert victim.preemptions == 1

    def test_resume_tokens_carry_generated_prefix(self):
        r = _req(0, 3, max_new=8)
        r.out_tokens = [11, 12, 13]
        r.next_token = 13
        resume = r.resume_tokens
        assert resume.tolist() == [0, 0, 0, 11, 12]  # pending 13 excluded


def _snapshot(sched, pool, cache):
    """Everything ``Scheduler.would_admit`` might have moved."""
    return ([r.rid for r in sched.waiting], [r.rid for r in sched.running],
            [(r.prefill_len, r.cache_len, r.state) for r in sched.waiting],
            sorted(pool._free), list(pool._evictable), dict(pool._ref),
            None if cache is None else dict(cache._index))


class TestWouldAdmit:
    """``Scheduler.would_admit`` is the head of ``schedule``'s own admission
    loop, asked without moving anything: on drawn states of every kind of
    pool it says what ``schedule`` then does, and leaves no trace."""

    KINDS = {
        "plain": dict(),
        "prefix_cache": dict(),
        "windowed": dict(window=32, chunk=4),
        "two_group": dict(groups=dict(window=16, full_layers=1,
                                      window_layers=4)),
    }

    @pytest.mark.parametrize("kind", sorted(KINDS))
    def test_agrees_with_schedule_and_moves_nothing(self, kind):
        rng = np.random.default_rng(sorted(self.KINDS).index(kind))
        answers = set()
        for _ in range(120):
            pool = PagedKVPool(1, 2, 4, int(rng.integers(12, 48)), 8,
                               **self.KINDS[kind])
            cache = None
            sched = Scheduler(max_batch_size=int(rng.integers(1, 5)),
                              token_budget=int(rng.integers(0, 40)),
                              chunk_size=int(rng.integers(4, 24)))
            if kind == "prefix_cache":
                cache = sched.prefix_cache = PrefixCache(8)
                pool.evictable_filter = cache.contains_block
                pool.reclaim_hook = cache.drop_blocks
                # a prompt served earlier: its blocks rest evictable
                shared = rng.integers(0, 50, 24).astype(np.int32)
                table = pool.alloc(3)
                cache.publish(shared, table, 24)
                pool.free(table)
            for rid in range(int(rng.integers(0, sched.max_batch_size + 1))):
                req = _req(rid, int(rng.integers(4, 40)),
                           max_new=int(rng.integers(1, 40)))
                req.prefill_len = len(req.prompt)
                req.cache_len = int(rng.integers(0, len(req.prompt) + 1))
                grow = (*pool.table_need(req.cache_len, 1),
                        pool.window_need(req.cache_len, 1, 0))
                if not pool.can_alloc(sum(grow)):
                    break
                req.block_table = pool.alloc(grow[0])
                req.summary_table = pool.alloc(grow[1])
                req.window_table = pool.alloc(grow[2])
                sched.admit(req)
            for rid in range(10, 10 + int(rng.integers(0, 4))):
                req = _req(rid, int(rng.integers(4, 60)),
                           max_new=int(rng.integers(1, 40)))
                if cache is not None and rng.random() < 0.5:
                    n = min(24, len(req.prompt))
                    req.prompt[:n] = shared[:n]
                sched.submit(req)
            before = _snapshot(sched, pool, cache)
            said = sched.would_admit(pool)
            assert _snapshot(sched, pool, cache) == before
            assert said == bool(sched.schedule(pool).prefills)
            answers.add(said)
        assert answers == {True, False}, "the draw never saw both answers"

    def test_blocks_taken_ahead_count_back_in(self):
        """``spare``: blocks the engine took for the running rows' steps
        ahead are blocks ``schedule`` would still have found free: a plain
        pool counts them back in, a pool that admits to the last token
        already counts them as no longer owed."""
        pool = PagedKVPool(1, 2, 4, 9, 4)
        sched = Scheduler(max_batch_size=2, token_budget=100)
        row = _req(0, 4)
        row.prefill_len = row.cache_len = 4
        row.block_table = pool.alloc(1) + pool.alloc(5)   # 5 taken ahead
        sched.admit(row)
        sched.submit(_req(1, 12))           # three blocks; two are free
        assert not sched.would_admit(pool)
        assert not sched.would_admit(pool, spare=0)
        assert sched.would_admit(pool, spare=1)
        windowed = PagedKVPool(1, 2, 4, 12, 8, window=32, chunk=4)
        sched = Scheduler(max_batch_size=2, token_budget=100)
        row = _req(0, 8, max_new=24)        # 4 exact pages + 1 of summaries
        row.prefill_len = row.cache_len = 8
        row.block_table = windowed.alloc(1)
        sched.admit(row)
        sched.submit(_req(1, 8, max_new=24))
        fits = sched.would_admit(windowed)
        row.block_table += windowed.alloc(2)            # taken ahead
        assert sched.would_admit(windowed, spare=2) == fits
        assert sched.would_admit(windowed) == fits

    def test_a_full_batch_answers_without_the_pool(self):
        sched = Scheduler(max_batch_size=1, token_budget=100)
        sched.admit(_req(0, 4))
        sched.submit(_req(1, 4))
        assert not sched.would_admit(None)      # the pool is never asked
        assert not Scheduler().would_admit(None)    # nor with nobody waiting


# -- end-to-end on a tiny model ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


# the ``family`` axis of the feature suites; ``lm`` (conftest) resolves a case
# to ``tiny_lm`` or ``llama_lm``
FAMILIES = ["gpt2", "llama"]


def _greedy_ref(model, params, prompt, max_new, max_len):
    from tnn_tpu.models.gpt2 import generate

    return np.asarray(generate(model, params, prompt[None], max_new,
                               max_len=max_len))[0].tolist()


class TestEngineTiny:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_staggered_parity(self, lm, family):
        model, params = lm
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32)
        rids = [eng.submit(prompts[0], 10)]
        eng.step(); eng.step()                        # r0 decodes alone
        rids += [eng.submit(p, 10) for p in prompts[1:]]
        out = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            assert out[rid] == _greedy_ref(model, params, p, 10,
                                           eng.assembly_len)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_preemption_recovers_exactly(self, lm, family):
        """A pool too small for all requests must preempt (recompute-requeue)
        and still produce byte-identical greedy outputs, ending drained."""
        model, params = lm
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng = InferenceEngine(model, params, num_blocks=9, block_size=4,
                              max_batch_size=4, max_seq_len=32)
        for p in prompts:
            eng.submit(p, 10)
        out = eng.run_until_complete()
        assert eng.metrics.preemptions > 0, "pool was never exhausted"
        for rid, p in enumerate(prompts):
            assert out[rid] == _greedy_ref(model, params, p, 10,
                                           eng.assembly_len)
        assert eng.pool.num_allocated == 0
        # drained: only free + prefix-cache-evictable blocks remain
        assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity

    @pytest.mark.parametrize("family", FAMILIES)
    def test_mixed_sampling_params(self, lm, family):
        """Greedy and stochastic requests share one decode batch; stochastic
        rows stay in-vocab and the run terminates."""
        model, params = lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32, seed=3)
        p = np.arange(6, dtype=np.int32)
        g = eng.submit(p, 8)
        s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
        out = eng.run_until_complete()
        assert out[g] == _greedy_ref(model, params, p, 8, eng.assembly_len)
        assert len(out[s]) == 8
        assert all(0 <= t < model.vocab_size for t in out[s])

    @pytest.mark.parametrize("family", FAMILIES)
    def test_stop_token_frees_early(self, lm, family):
        model, params = lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=2, max_seq_len=32)
        p = np.arange(5, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 10, eng.assembly_len)
        stop = ref[3]
        rid = eng.submit(p, 10, stop_token=stop)
        out = eng.run_until_complete()
        assert out[rid] == ref[:4]
        assert eng.result(rid).finish_reason == "stop_token"
        assert eng.pool.num_allocated == 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_paged_parity_staggered(self, lm, family):
        """The step programs attend the pool's pages through block tables
        and never assemble a cache: under staggered admission (ragged
        offsets) every stream equals the offline reference, which does."""
        model, params = lm
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32)
        rids = [eng.submit(prompts[0], 10)]
        eng.step(); eng.step()
        rids += [eng.submit(p, 10) for p in prompts[1:]]
        out = eng.run_until_complete()
        assert eng.stats()["decode_path"] == "paged"
        assert eng.paged_fallback_reason is None
        assert {k[0] for k in eng._jit} == {"pdecode", "mixed"}
        for rid, p in zip(rids, prompts):
            assert out[rid] == _greedy_ref(model, params, p, 10,
                                           eng.assembly_len)

    @pytest.mark.parametrize("family", FAMILIES + ["mistral"])
    def test_paged_preemption_parity(self, lm, family):
        """Preemption-recovery (recompute-requeue) leaves every stream
        byte-identical to the offline reference, with the prefix cache off:
        every resumed request re-prefills its whole extended prompt."""
        model, params = lm
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng = InferenceEngine(model, params, num_blocks=9, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              prefix_cache=False)
        for p in prompts:
            eng.submit(p, 10)
        out = eng.run_until_complete()
        assert eng.metrics.preemptions > 0, "pool was never exhausted"
        for rid, p in enumerate(prompts):
            assert out[rid] == _greedy_ref(model, params, p, 10,
                                           eng.assembly_len)
        assert eng.pool.num_allocated == 0
        assert eng.pool.num_free == eng.pool.capacity

    @pytest.mark.parametrize("family", FAMILIES)
    def test_paged_mixed_sampling(self, lm, family):
        """Stochastic rows ride the paged step too: the same engine seed
        gives the same stream twice, and every sampled token lies in the
        top-k of the reference forward's distribution at its position."""
        model, params = lm
        p = np.arange(6, dtype=np.int32)

        def run():
            eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                                  max_batch_size=4, max_seq_len=32, seed=3)
            g = eng.submit(p, 8)
            s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
            out = eng.run_until_complete()
            return out[g], out[s]

        first = run()
        assert first == run()
        greedy, sampled = first
        assert greedy == _greedy_ref(model, params, p, 8, 32)
        ids = jnp.asarray(list(p) + sampled)[None]
        logits, _ = model.apply({"params": params, "state": {}}, ids)
        rows = np.asarray(logits[0, len(p) - 1:-1])
        for tok, row in zip(sampled, rows):
            assert tok in np.argsort(row)[-16:]

    def test_submit_validation(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, num_blocks=4, block_size=4,
                              max_batch_size=2, max_seq_len=12)
        with pytest.raises(ValueError):
            eng.submit(np.arange(10, dtype=np.int32), 8)   # > max_seq_len
        with pytest.raises(ValueError):
            eng.submit(np.asarray([], np.int32), 4)        # empty prompt
        with pytest.raises(ValueError):
            eng.submit(np.arange(4, dtype=np.int32), 0)    # no tokens asked


# -- chunked prefill: the mixed prefill+decode step --------------------------


class TestChunkedPrefill:
    """The PR 4 tentpole: prompts advance chunk_size tokens per step inside
    the SAME compiled program as the decode rows. Every schedule must stay
    token-exact against the offline reference (``models.gpt2.generate``,
    which pushes the whole prompt through one forward), with and without
    preemption."""

    def _run(self, lm, prompts, *, stagger=True, **kw):
        model, params = lm
        merged = dict(num_blocks=32, block_size=4, max_batch_size=4,
                      max_seq_len=32)
        merged.update(kw)
        eng = InferenceEngine(model, params, **merged)
        rids = [eng.submit(prompts[0], 10)]
        if stagger:
            eng.step(); eng.step()          # r0 mid-stream before the rest
        rids += [eng.submit(p, 10) for p in prompts[1:]]
        out = eng.run_until_complete()
        return eng, [out[r] for r in rids]

    @pytest.mark.parametrize("family", FAMILIES)
    def test_chunked_matches_whole_staggered(self, lm, family):
        """chunk_size=4 splits the 9/16-token prompts across several mixed
        steps; outputs must equal the offline reference, whose prefill is
        one whole-prompt forward."""
        model, params = lm
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng_c, chunked = self._run(lm, prompts, chunk_size=4)
        for toks, p in zip(chunked, prompts):
            assert toks == _greedy_ref(model, params, p, 10,
                                       eng_c.assembly_len)
        # the 16-token prompt really took several chunks, and the only
        # programs are the decode step and the mixed steps
        assert eng_c.metrics.prefill_chunks >= 4 + 3 + 2 + 2
        assert {k[0] for k in eng_c._jit} == {"pdecode", "mixed"}
        _assert_drained(eng_c)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_chunked_preemption_recovers_exactly(self, lm, family):
        """A starved pool preempts mid-stream; partially-prefilled work is
        re-chunked on resume and every stream stays byte-identical."""
        model, params = lm
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, p).astype(np.int32)
                   for p in (5, 9, 16, 7)]
        eng, outs = self._run(lm, prompts, stagger=False,
                              num_blocks=9, chunk_size=4)
        assert eng.metrics.preemptions > 0, "pool was never exhausted"
        for toks, p in zip(outs, prompts):
            assert toks == _greedy_ref(model, params, p, 10,
                                       eng.assembly_len)
        _assert_drained(eng)

    def test_mixed_bucketing_bounds_compiles(self, tiny_lm):
        """Chunk takes quantize to power-of-two query widths: many distinct
        prompt lengths share O(log chunk_size) compiled mixed programs."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32, chunk_size=8)
        for n in (1, 2, 3, 4, 5, 7, 9, 11, 13, 15):
            eng.submit(np.arange(n, dtype=np.int32) % 128, 2)
        eng.run_until_complete()
        widths = {k[2] for k in eng._jit if k[0] == "mixed"}
        assert widths, "mixed step never ran"
        assert widths <= {1, 2, 4, 8}      # pow2 buckets, capped by chunk_size
        _assert_drained(eng)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_mixed_sampling_in_chunked_steps(self, lm, family):
        """Greedy and stochastic rows share mixed steps with in-flight prompt
        chunks; the greedy stream stays exact and stochastic rows stay
        in-vocab."""
        model, params = lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32, seed=3,
                              chunk_size=4)
        p = np.arange(9, dtype=np.int32)
        g = eng.submit(p, 8)
        s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
        out = eng.run_until_complete()
        assert out[g] == _greedy_ref(model, params, p, 8, eng.assembly_len)
        assert len(out[s]) == 8
        assert all(0 <= t < model.vocab_size for t in out[s])
        _assert_drained(eng)


# -- acceptance: gpt2_small, 8 staggered requests ----------------------------


def _assert_teacher_forced(model, params, prompts, outs):
    """Feed each prompt plus the engine's output through one plain reference
    forward and require every engine token to be the argmax there (a handful
    of fp near-ties allowed)."""
    seqs = np.stack([np.concatenate([p, o]) for p, o in zip(prompts, outs)])
    caches = model.init_cache(len(outs), seqs.shape[1])
    logits, _ = model.apply_cached(params, jnp.asarray(seqs), caches, 0)
    logits = np.asarray(logits, np.float64)
    plen, max_new = prompts.shape[1], len(outs[0])
    exact, ties = 0, []
    for i in range(len(outs)):
        for j in range(max_new):
            row = logits[i, plen + j - 1]
            chosen = seqs[i, plen + j]
            if chosen == row.argmax():
                exact += 1
            else:
                ties.append(float(row.max() - row[chosen]))
    total = len(outs) * max_new
    # measured: 124/128 exact, worst near-tie margin 0.0088 — far under the
    # ~0.01+ top-2 gaps a non-greedy bug would violate
    assert exact >= 0.9 * total, f"only {exact}/{total} tokens were argmax"
    assert all(m < 0.05 for m in ties), f"non-tie divergence: {ties}"


@pytest.mark.slow
def test_gpt2_small_staggered_greedy():
    """The ISSUE's acceptance bar: >= 8 concurrent requests on gpt2_small
    (CPU), staggered submissions, greedy decoding, surviving pool exhaustion
    via preemption.

    Greedy correctness is asserted by TEACHER FORCING: feed each prompt plus
    the engine's output through one plain reference forward and require every
    engine token to be the argmax there (a handful of fp near-ties allowed).
    Whole-sequence equality against generate() is ill-posed on random weights
    at this depth: top-2 logit gaps run ~0.01-0.07 (std 0.55), below the f32
    reduction-order noise of differently-fused XLA programs — generate()
    itself emits different greedy tokens at batch 8 vs batch 1. Exact
    token-for-token parity is asserted on the tiny model above, where the
    gaps dwarf the noise (TestEngineTiny covers staggered AND preemption)."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, model.vocab_size, (8, 12)).astype(np.int32)
    max_new = 16

    # pool sized so 8 requests of 28 tokens (2 blocks each) exhaust it:
    # 13 usable blocks < 8 * 2 -> preemption must fire and recover
    eng = InferenceEngine(model, params, num_blocks=14, block_size=16,
                          max_batch_size=8, max_seq_len=32)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if i % 3 == 2:
            eng.step()  # staggered: some decode before others submit
    out = eng.run_until_complete()

    assert eng.metrics.preemptions > 0, "pool was never exhausted"
    assert eng.pool.num_allocated == 0
    assert all(len(out[rid]) == max_new for rid in rids)

    _assert_teacher_forced(model, params, prompts, [out[r] for r in rids])


@pytest.mark.slow
def test_gpt2_small_paged_matches_standard():
    """Acceptance bar for the serving path with the prefix cache off: on
    gpt2_small, staggered submissions with preemption, every resumed request
    re-prefills its whole extended prompt, and every token is the argmax of
    the plain reference forward at its position (teacher forcing, as above:
    a wrong page read or write, an off-by-one kv length or a table mix-up
    shows as a non-tie divergence)."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, model.vocab_size, (8, 12)).astype(np.int32)
    max_new = 16
    eng = InferenceEngine(model, params, num_blocks=14, block_size=16,
                          max_batch_size=8, max_seq_len=32,
                          prefix_cache=False)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if i % 3 == 2:
            eng.step()
    out = eng.run_until_complete()
    assert eng.metrics.preemptions > 0, "pool was never exhausted"
    _assert_teacher_forced(model, params, prompts, [out[r] for r in rids])
    assert eng.pool.num_allocated == 0


@pytest.mark.slow
def test_gpt2_small_chunked_paged_matches_standard():
    """Chunked-prefill acceptance on gpt2_small: chunk_size=8 splits every
    12-token prompt across two mixed steps, the pool preempts under load,
    and every token is the argmax of the plain reference forward at its
    position (a wrong ragged query gather, chunk write or per-row kv length
    shows as a non-tie divergence)."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, model.vocab_size, (8, 12)).astype(np.int32)
    max_new = 16
    eng = InferenceEngine(model, params, num_blocks=14, block_size=16,
                          max_batch_size=8, max_seq_len=32, chunk_size=8)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if i % 3 == 2:
            eng.step()
    out = eng.run_until_complete()
    assert eng.metrics.preemptions > 0, "pool was never exhausted"
    assert eng.metrics.prefill_chunks > len(prompts), "prompts never split"
    _assert_teacher_forced(model, params, prompts, [out[r] for r in rids])
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity


# -- fault tolerance: invariants, lifecycle, backpressure, chaos --------------


def _assert_drained(eng):
    """The chaos invariant: every submitted request terminal, no leaked
    blocks, bookkeeping clean. With the prefix cache on (the default),
    a drained pool may hold zero-ref EVICTABLE blocks — reclaimable cached
    KV — so the partition is free + evictable == capacity, allocated 0."""
    states = {r.rid: r.state for r in eng.requests.values()}
    assert all(s in TERMINAL_STATES for s in states.values()), states
    assert not eng.has_work
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity
    if eng.prefix_cache is None:
        assert eng.pool.num_evictable == 0
    eng.check_invariants()


def _finished(eng):
    return {rid: list(r.out_tokens) for rid, r in eng.requests.items()
            if r.state is RequestState.FINISHED}


class TestPoolInvariants:
    def _pool(self):
        return PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=2,
                           num_blocks=8, block_size=4)

    def test_clean_pool_passes(self):
        pool = self._pool()
        blocks = pool.alloc(3)
        pool.check_invariants()
        pool.check_invariants([blocks])
        pool.free(blocks)
        pool.check_invariants([])

    def test_double_circulation_detected(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        pool._free.append(blocks[0])      # corrupt: free AND allocated
        with pytest.raises(ValueError, match="both free and allocated"):
            pool.check_invariants()

    def test_scratch_never_circulates(self):
        pool = self._pool()
        pool._ref[PagedKVPool.SCRATCH] = 1
        with pytest.raises(ValueError, match="scratch"):
            pool.check_invariants()

    def test_leak_detected_via_tables(self):
        """A block allocated but owned by no live table is a leak."""
        pool = self._pool()
        blocks = pool.alloc(2)
        with pytest.raises(ValueError, match="leaked"):
            pool.check_invariants([])     # nobody claims `blocks`
        pool.check_invariants([blocks])   # claimed: clean
        del blocks

    def test_overshared_block_detected(self):
        pool = self._pool()
        blocks = pool.alloc(1)
        with pytest.raises(ValueError, match="mismatch"):
            pool.check_invariants([blocks, blocks])  # refcount 1, 2 tables
        pool.fork(blocks)
        pool.check_invariants([blocks, blocks])      # refcount 2: fine

    def test_count_mismatch_detected(self):
        pool = self._pool()
        pool._free.pop()                  # block vanishes entirely
        with pytest.raises(ValueError, match="capacity"):
            pool.check_invariants()

    def test_debug_mode_checks_on_free(self, monkeypatch):
        monkeypatch.setenv("TNN_POOL_DEBUG", "1")
        pool = self._pool()
        assert pool.debug
        a = pool.alloc(2)
        pool.free(a)                      # clean: no raise
        b = pool.alloc(1)
        pool._free.append(b[0])           # corrupt behind the pool's back
        with pytest.raises(ValueError):
            pool.free(b)


class TestFaultPlan:
    def test_nth_call_alloc_failure_is_exact(self):
        plan = FaultPlan(alloc_fail_calls=(3,))
        pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=2,
                           num_blocks=8, block_size=4)
        pool.fault_plan = plan
        pool.free(pool.alloc(1))
        pool.free(pool.alloc(1))
        with pytest.raises(PoolExhausted, match="injected"):
            pool.alloc(1)
        pool.free(pool.alloc(1))          # call 4: passes again
        assert plan.calls["pool.alloc"] == 4
        assert plan.fired["pool.alloc"] == 1
        pool.check_invariants()           # rejected alloc mutated nothing

    def test_seeded_plans_are_deterministic(self):
        def trace(plan):
            fires = []
            for _ in range(64):
                try:
                    plan.on_alloc(1, 8)
                    fires.append(False)
                except PoolExhausted:
                    fires.append(True)
            return fires

        a = trace(FaultPlan(seed=11, alloc_fail_prob=0.3))
        b = trace(FaultPlan(seed=11, alloc_fail_prob=0.3))
        c = trace(FaultPlan(seed=12, alloc_fail_prob=0.3))
        assert a == b
        assert any(a) and not all(a)
        assert a != c                     # different seed, different schedule

    def test_poison_rows_nth_call_hits_row_zero(self):
        plan = FaultPlan(nan_logit_calls=(2,))
        assert not plan.poison_rows(3).any()
        mask = plan.poison_rows(3)
        assert mask.tolist() == [True, False, False]
        assert plan.fired["decode.logits"] == 1

    def test_connection_sites_are_deterministic(self):
        """The client-side sites (disconnect / slow / malformed) draw from
        the same seeded rng as the engine sites: identical seeds produce
        identical fire traces, so a chaos soak replays bit-for-bit."""
        def trace(plan):
            return [(plan.client_disconnect(), plan.slow_consumer(),
                     plan.malformed_request()) for _ in range(48)]

        kw = dict(client_disconnect_prob=0.3, slow_consumer_prob=0.25,
                  malformed_request_prob=0.2)
        a = trace(FaultPlan(seed=5, **kw))
        b = trace(FaultPlan(seed=5, **kw))
        c = trace(FaultPlan(seed=6, **kw))
        assert a == b
        assert a != c
        assert any(t[0] for t in a) and any(t[1] for t in a) \
            and any(t[2] for t in a)
        plan = FaultPlan(seed=5, **kw)
        trace(plan)
        assert plan.calls["client.disconnect"] == 48
        assert plan.fired["client.disconnect"] == sum(t[0] for t in a)
        assert plan.fired["client.slow"] == sum(t[1] for t in a)
        assert plan.fired["client.malformed"] == sum(t[2] for t in a)

    def test_scheduled_connection_calls_fire_exactly(self):
        plan = FaultPlan(client_disconnect_calls=(2,),
                         malformed_request_calls=(1, 3))
        assert [plan.client_disconnect() for _ in range(3)] == \
            [False, True, False]
        assert [plan.malformed_request() for _ in range(3)] == \
            [True, False, True]

    def test_replica_sites_are_deterministic(self):
        """The router-side sites (replica.kill / net.delay / net.drop) draw
        from the same seeded rng: identical seeds replay identical kill and
        network-fault schedules, so a failover soak is reproducible."""
        def trace(plan):
            return [(plan.replica_kill(), plan.net_delay(), plan.net_drop())
                    for _ in range(48)]

        kw = dict(replica_kill_prob=0.2, net_delay_prob=0.3,
                  net_drop_prob=0.25)
        a = trace(FaultPlan(seed=5, **kw))
        b = trace(FaultPlan(seed=5, **kw))
        c = trace(FaultPlan(seed=6, **kw))
        assert a == b
        assert a != c
        assert any(t[0] for t in a) and any(t[1] for t in a) \
            and any(t[2] for t in a)
        plan = FaultPlan(seed=5, **kw)
        trace(plan)
        assert plan.calls["replica.kill"] == 48
        assert plan.fired["replica.kill"] == sum(t[0] for t in a)
        assert plan.fired["net.delay"] == sum(t[1] for t in a)
        assert plan.fired["net.drop"] == sum(t[2] for t in a)

    def test_scheduled_replica_calls_fire_exactly(self):
        plan = FaultPlan(replica_kill_calls=(3,), net_drop_calls=(1, 2))
        assert [plan.replica_kill() for _ in range(4)] == \
            [False, False, True, False]
        assert [plan.net_drop() for _ in range(3)] == [True, True, False]
        assert plan.fired["replica.kill"] == 1
        assert plan.fired["net.drop"] == 2

    def test_step_crash_fires_at_exact_call_and_escapes(self):
        """EngineCrash is deliberately NOT FaultInjected — nothing inside
        the engine may catch it (only the supervisor recovers)."""
        from tnn_tpu.serving import FaultInjected

        plan = FaultPlan(step_crash_calls=(3,))
        plan.on_step()
        plan.on_step()
        with pytest.raises(EngineCrash, match="step #3"):
            plan.on_step()
        plan.on_step()                    # call 4: passes again
        assert plan.fired["engine.step"] == 1
        assert not issubclass(EngineCrash, FaultInjected)

    def test_step_delay_calls_select_steps(self):
        plan = FaultPlan(step_delay_s=0.02, step_delay_calls=(2,))
        t0 = time.perf_counter()
        plan.on_step()
        fast = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan.on_step()
        slow = time.perf_counter() - t0
        assert slow >= 0.02 > fast


class TestLifecycle:
    """Cancellation, deadlines, and bounded admission on the tiny model."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def test_cancel_while_queued(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=1, max_seq_len=32)
        p = np.arange(5, dtype=np.int32)
        r0 = eng.submit(p, 6)
        eng.step()                                  # r0 admitted
        r1 = eng.submit(p, 6)                       # stuck behind r0 (batch 1)
        assert eng.cancel(r1)
        assert eng.result(r1).state is RequestState.CANCELLED
        out = eng.run_until_complete()
        assert out[r0] == _greedy_ref(model, params, p, 6, eng.assembly_len)
        assert r1 not in out
        assert eng.metrics.cancelled == 1
        _assert_drained(eng)

    def test_cancel_while_running_frees_blocks(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        rid = eng.submit(np.arange(6, dtype=np.int32), 20)
        eng.step()
        assert eng.result(rid).state is RequestState.RUNNING
        assert eng.pool.num_allocated > 0
        assert eng.cancel(rid)
        assert eng.result(rid).state is RequestState.CANCELLED
        _assert_drained(eng)

    def test_cancel_terminal_or_unknown_is_noop(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        rid = eng.submit(np.arange(4, dtype=np.int32), 2)
        eng.run_until_complete()
        assert not eng.cancel(rid)                  # already FINISHED
        assert not eng.cancel(12345)                # never existed
        assert eng.result(rid).state is RequestState.FINISHED

    def test_deadline_expires_while_queued(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        rid = eng.submit(np.arange(4, dtype=np.int32), 4, deadline_s=0.0)
        events = eng.step()
        assert [rid_ for rid_, _ in events["timed_out"]] == [rid]
        req = eng.result(rid)
        assert req.state is RequestState.TIMED_OUT
        assert "deadline" in req.error
        assert eng.metrics.timed_out == 1
        _assert_drained(eng)

    def test_deadline_expires_while_running(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        rid = eng.submit(np.arange(4, dtype=np.int32), 25, deadline_s=0.15)
        eng.step()
        assert eng.result(rid).state is RequestState.RUNNING
        time.sleep(0.2)
        eng.step()
        req = eng.result(rid)
        assert req.state is RequestState.TIMED_OUT
        assert req.out_tokens, "made progress before the deadline"
        _assert_drained(eng)

    def test_max_queue_s_expires_only_queued(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=1, max_seq_len=32)
        p = np.arange(4, dtype=np.int32)
        r0 = eng.submit(p, 6)
        eng.step()                                  # r0 running
        r1 = eng.submit(p, 6, max_queue_s=0.0)      # expires at next step
        eng.step()
        assert eng.result(r1).state is RequestState.TIMED_OUT
        assert "max_queue_s" in eng.result(r1).error
        out = eng.run_until_complete()
        assert out[r0] == _greedy_ref(model, params, p, 6, eng.assembly_len)
        _assert_drained(eng)

    def test_admission_reject_backpressure(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, max_queue_depth=2,
                              admission_policy="reject", **self.KW)
        p = np.arange(4, dtype=np.int32)
        eng.submit(p, 4)
        eng.submit(p, 4)
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(p, 4)
        assert ei.value.queue_depth == 2
        assert ei.value.max_queue_depth == 2
        assert eng.metrics.rejected == 1
        assert len(eng.requests) == 2               # rejected never entered
        eng.run_until_complete()
        _assert_drained(eng)

    def test_admission_block_drains_then_accepts(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, max_queue_depth=1,
                              admission_policy="block", **self.KW)
        p = np.arange(4, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 6, eng.assembly_len)
        rids = [eng.submit(p, 6) for _ in range(4)]  # blocks, never raises
        out = eng.run_until_complete()
        assert [out[r] for r in rids] == [ref] * 4
        assert eng.metrics.rejected == 0
        _assert_drained(eng)

    def test_preemption_budget_fails_victim_cleanly(self, tiny_lm):
        """With budget 0 the first would-be preemption victim FAILs (blocks
        freed) instead of thrashing; everyone else still finishes exactly."""
        model, params = tiny_lm
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 9, 16, 7)]
        eng = InferenceEngine(model, params, num_blocks=9, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              preemption_budget=0)
        rids = [eng.submit(p, 10) for p in prompts]
        eng.run_until_complete()
        failed = [r for r in eng.requests.values()
                  if r.state is RequestState.FAILED]
        assert failed, "pool never filled — scenario broken"
        assert all("preemption budget" in r.error for r in failed)
        assert eng.metrics.preemptions == 0
        assert eng.metrics.failed == len(failed)
        out = _finished(eng)
        for rid, p in zip(rids, prompts):
            if rid in out:
                assert out[rid] == _greedy_ref(model, params, p, 10,
                                               eng.assembly_len)
        _assert_drained(eng)

    def test_stats_shape(self, tiny_lm):
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        rid = eng.submit(np.arange(4, dtype=np.int32), 3)
        eng.cancel(rid)
        eng.submit(np.arange(4, dtype=np.int32), 3)
        eng.run_until_complete()
        s = eng.stats()
        assert s["requests_cancelled"] == 1
        assert s["requests_finished"] == 1
        assert s["cancelled"] == 1
        assert s["pool_allocated_blocks"] == 0
        assert s["queue_depth"] == 0 and s["num_running"] == 0
        assert s["decode_path"] == "paged"


class TestChaos:
    """Seeded FaultPlan runs: every request reaches a terminal state,
    survivors are token-identical to a fault-free run, zero leaked blocks."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _prompts(self, n, seed=0):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, 128, int(l)).astype(np.int32)
                for l in rng.integers(4, 14, n)]

    def _run(self, model, params, prompts, max_new=8, plan=None, **kw):
        merged = dict(self.KW)
        merged.update(kw)
        eng = InferenceEngine(model, params, faults=plan, **merged)
        rids = [eng.submit(p, max_new) for p in prompts]
        eng.run_until_complete()
        return eng, rids

    def test_alloc_failure_mid_prefill_is_isolated(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._prompts(3)
        ref_eng, ref_rids = self._run(model, params, prompts)
        plan = FaultPlan(alloc_fail_calls=(2,))     # r1's prefill alloc
        eng, rids = self._run(model, params, prompts, plan=plan)
        assert eng.result(rids[1]).state is RequestState.FAILED
        assert "injected allocation failure" in eng.result(rids[1]).error
        out, ref = _finished(eng), _finished(ref_eng)
        for i in (0, 2):
            assert out[rids[i]] == ref[ref_rids[i]]
        _assert_drained(eng)

    def test_alloc_failure_mid_decode_is_isolated(self, tiny_lm):
        """Growth alloc fails for one request mid-decode; the other finishes
        token-exact — a pool fault no longer aborts unrelated requests."""
        model, params = tiny_lm
        p = np.arange(6, dtype=np.int32)
        ref_eng, ref_rids = self._run(model, params, [p, p])
        # alloc calls: prefill r0 (1), prefill r1 (2), growth r0 (3), ...
        plan = FaultPlan(alloc_fail_calls=(3,))
        eng, rids = self._run(model, params, [p, p], plan=plan)
        assert eng.result(rids[0]).state is RequestState.FAILED
        assert "mid-decode" in eng.result(rids[0]).error
        assert _finished(eng)[rids[1]] == _finished(ref_eng)[ref_rids[1]]
        assert plan.fired["pool.alloc"] == 1
        _assert_drained(eng)

    def test_alloc_failure_at_chunk_boundary_is_isolated(self, tiny_lm):
        """A chunked prompt's block alloc fails at a chunk boundary (between
        chunk 1 and chunk 2): only that request FAILs, its partial blocks are
        freed, and the co-scheduled request finishes token-exact."""
        model, params = tiny_lm
        prompts = [np.arange(12, dtype=np.int32),    # 3 chunks at chunk_size 4
                   np.arange(4, dtype=np.int32)]     # 1 chunk
        ref_eng, ref_rids = self._run(model, params, prompts, chunk_size=4)
        # alloc calls: step1 chunk r0 (1), chunk r1 (2); step2 chunk r0 (3)
        plan = FaultPlan(alloc_fail_calls=(3,))
        eng, rids = self._run(model, params, prompts, plan=plan,
                              chunk_size=4)
        victim = eng.result(rids[0])
        assert victim.state is RequestState.FAILED
        assert "at chunk boundary" in victim.error
        assert not victim.out_tokens, "failed mid-prefill, before any token"
        assert plan.fired["pool.alloc"] == 1
        assert _finished(eng)[rids[1]] == _finished(ref_eng)[ref_rids[1]]
        _assert_drained(eng)

    def test_nan_logits_in_decode_fail_one_row(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._prompts(3, seed=2)
        ref_eng, ref_rids = self._run(model, params, prompts)
        plan = FaultPlan(nan_logit_calls=(2,))      # row 0 of decode call 2
        eng, rids = self._run(model, params, prompts, plan=plan)
        victim = eng.result(rids[0])
        assert victim.state is RequestState.FAILED
        assert "non-finite logits" in victim.error
        assert victim.out_tokens, "failed after producing valid tokens"
        out, ref = _finished(eng), _finished(ref_eng)
        for i in (1, 2):
            assert out[rids[i]] == ref[ref_rids[i]]
        _assert_drained(eng)

    def test_nan_logits_in_prefill_fail_request(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._prompts(3, seed=3)
        ref_eng, ref_rids = self._run(model, params, prompts)
        plan = FaultPlan(nan_prefill_calls=(2,))
        eng, rids = self._run(model, params, prompts, plan=plan)
        assert eng.result(rids[1]).state is RequestState.FAILED
        assert "prefill" in eng.result(rids[1]).error
        out, ref = _finished(eng), _finished(ref_eng)
        for i in (0, 2):
            assert out[rids[i]] == ref[ref_rids[i]]
        _assert_drained(eng)

    def test_logit_guard_can_be_disabled(self, tiny_lm):
        """With the guard off a poisoned row is NOT failed — the garbage
        token streams through (caller's choice to run unguarded)."""
        model, params = tiny_lm
        plan = FaultPlan(nan_logit_calls=(2,))
        eng, rids = self._run(model, params, self._prompts(2, seed=4),
                              plan=plan, logit_guard=False)
        assert all(eng.result(r).state is RequestState.FINISHED
                   for r in rids)
        _assert_drained(eng)

    def test_transient_step_exception_retries_exactly(self, tiny_lm):
        """A transient decode fault is retried with the SAME key: outputs
        are bit-identical to a fault-free run — the fault is invisible."""
        model, params = tiny_lm
        prompts = self._prompts(3, seed=5)
        ref_eng, ref_rids = self._run(model, params, prompts)
        plan = FaultPlan(decode_exc_calls=(2,), transient_exc=True)
        eng, rids = self._run(model, params, prompts, plan=plan)
        assert plan.fired["decode"] == 1
        assert eng.metrics.step_retries == 1
        out, ref = _finished(eng), _finished(ref_eng)
        assert [out[r] for r in rids] == [ref[r] for r in ref_rids]
        _assert_drained(eng)

    def test_persistent_step_exception_aborts_batch_only(self, tiny_lm):
        """A hard decode failure fails the LIVE batch but the engine keeps
        serving: queued requests still complete token-exact."""
        model, params = tiny_lm
        p = np.arange(6, dtype=np.int32)
        ref_eng, ref_rids = self._run(model, params, [p, p, p],
                                      max_batch_size=2)
        plan = FaultPlan(decode_exc_calls=(1,), transient_exc=False)
        eng, rids = self._run(model, params, [p, p, p], plan=plan,
                              max_batch_size=2)
        for r in rids[:2]:                          # the aborted batch
            assert eng.result(r).state is RequestState.FAILED
            assert "injected persistent fault" in eng.result(r).error
        assert _finished(eng)[rids[2]] == _finished(ref_eng)[ref_rids[2]]
        _assert_drained(eng)

    def test_chaos_gate(self, tiny_lm):
        """The acceptance gate: >=10% pool-alloc failure probability plus
        injected NaN logits on the tiny gpt2. Every submitted request must
        reach a terminal state, survivors must be token-identical to a
        fault-free run, and the pool must end with zero leaked blocks."""
        model, params = tiny_lm
        prompts = self._prompts(8, seed=6)
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32)
        ref_eng, ref_rids = self._run(model, params, prompts, **kw)
        plan = FaultPlan(seed=9, alloc_fail_prob=0.12, nan_logit_calls=(5,))
        eng, rids = self._run(model, params, prompts, plan=plan, **kw)
        assert plan.fired["pool.alloc"] >= 1, "chaos never fired — dead test"
        states = [eng.result(r).state for r in rids]
        assert all(s in TERMINAL_STATES for s in states)
        assert RequestState.FAILED in states, "no request failed"
        assert RequestState.FINISHED in states, "no request survived"
        out, ref = _finished(eng), _finished(ref_eng)
        for rid, ref_rid in zip(rids, ref_rids):
            if rid in out:
                assert out[rid] == ref[ref_rid], f"survivor {rid} diverged"
        _assert_drained(eng)

    def test_chaos_gate_llama(self, llama_lm):
        """Same gate over the other family's block (rotary, RMSNorm, gated
        feed-forward, grouped heads): its compiled step and KV plumbing must
        honor the same isolation."""
        model, params = llama_lm
        prompts = self._prompts(6, seed=7)
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32)
        ref_eng, ref_rids = self._run(model, params, prompts, **kw)
        plan = FaultPlan(seed=13, alloc_fail_prob=0.12, nan_logit_calls=(4,))
        eng, rids = self._run(model, params, prompts, plan=plan, **kw)
        assert plan.fired["pool.alloc"] >= 1
        out, ref = _finished(eng), _finished(ref_eng)
        for rid, ref_rid in zip(rids, ref_rids):
            if rid in out:
                assert out[rid] == ref[ref_rid]
        _assert_drained(eng)


# -- prefix cache: hash-chain index, evictable pool, engine-level reuse -------


class TestPrefixCacheIndex:
    """Host-side hash-chain unit tests — no engine, no device arrays."""

    def test_chain_commits_to_whole_prefix(self):
        pc = PrefixCache(block_size=4)
        a = np.arange(8, dtype=np.int32)
        b = a.copy()
        b[0] ^= 1                       # differ only inside block 0
        ka, kb = pc.chain_keys(a), pc.chain_keys(b)
        assert ka[0] != kb[0]
        assert ka[1] != kb[1], "block-1 key must commit to the whole prefix"

    def test_no_false_sharing_on_divergent_prefix(self):
        """Identical block-1 TOKENS under a different block 0 must not match
        block 1 — the chain key commits to the entire preceding prefix."""
        pc = PrefixCache(block_size=4)
        a = np.arange(12, dtype=np.int32)
        pc.publish(a, [3, 4, 5], 8)     # blocks 0 and 1 of `a` indexed
        b = a.copy()
        b[0] ^= 1                       # blocks 1+ identical to a's
        assert pc.probe(b) == ([], 0, False)

    def test_probe_returns_longest_indexed_chain(self):
        pc = PrefixCache(block_size=4)
        toks = np.arange(12, dtype=np.int32)
        assert pc.probe(toks) == ([], 0, False)
        pc.publish(toks, [5, 6, 7], 12)
        ext = np.concatenate([toks, np.asarray([99], np.int32)])
        assert pc.probe(ext) == ([5, 6, 7], 12, False)
        div = ext.copy()
        div[9] ^= 1                     # diverges inside block 2
        assert pc.probe(div) == ([5, 6], 8, False)

    def test_full_cover_probe_caps_for_cow(self):
        """A fully-cached prompt still recomputes >= 1 token (it needs
        logits to sample its first output), so probe caps cached_len at
        total - 1 and flags that blocks[-1] needs a private COW copy."""
        pc = PrefixCache(block_size=4)
        toks = np.arange(8, dtype=np.int32)
        pc.publish(toks, [3, 4], 8)
        assert pc.probe(toks) == ([3, 4], 7, True)

    def test_min_hit_blocks_filters_short_matches(self):
        pc = PrefixCache(block_size=4, min_hit_blocks=2)
        toks = np.arange(12, dtype=np.int32)
        pc.publish(toks, [3, 4], 4)     # only block 0 is full-published
        assert pc.probe(toks) == ([], 0, False)
        pc.publish(toks, [3, 4], 8)     # now a 2-block chain
        assert pc.probe(toks) == ([3, 4], 8, False)

    def test_publish_first_wins_and_partial_excluded(self):
        pc = PrefixCache(block_size=4)
        toks = np.arange(10, dtype=np.int32)
        assert pc.publish(toks, [3, 4, 5], 10) == 2  # block 2 partial: skipped
        assert pc.publish(toks, [8, 9, 10], 10) == 0  # twin loses: dedupe
        assert pc.probe(toks)[0] == [3, 4]

    def test_drop_blocks_breaks_chain_at_parent(self):
        pc = PrefixCache(block_size=4)
        toks = np.arange(8, dtype=np.int32)
        pc.publish(toks, [3, 4], 8)
        pc.drop_blocks([3])             # parent reclaimed
        ext = np.concatenate([toks, np.asarray([9], np.int32)])
        assert pc.probe(ext) == ([], 0, False)   # probe walks from block 0
        assert len(pc) == 1 and pc.contains_block(4)  # orphaned child entry
        pc.drop_blocks([4, 99])         # unknown ids tolerated
        assert len(pc) == 0 and not pc.contains_block(4)


class TestEvictablePool:
    """free() parks zero-ref cache-indexed blocks in an evictable LRU;
    alloc() reclaims them on demand — cached KV never shrinks capacity."""

    def _pool(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("head_dim", 2)
        kw.setdefault("num_blocks", 8)
        kw.setdefault("block_size", 4)
        pool = PagedKVPool(**kw)
        pool.evictable_filter = lambda b: True   # every block "indexed"
        return pool

    def test_free_parks_then_alloc_reclaims_lru(self):
        pool = self._pool()
        a = pool.alloc(3)
        pool.free(a)
        assert pool.num_evictable == 3 and pool.num_free == 4
        assert pool.num_allocated == 0 and pool.num_allocatable == 7
        pool.check_invariants([])
        reclaimed = []
        pool.reclaim_hook = reclaimed.extend
        pool.alloc(6)                   # needs 2 beyond the free list
        # free() parks deepest-first, so the LRU-oldest blocks are the
        # chain TAIL: a[2] then a[1] go first, the parent a[0] survives
        assert reclaimed == [a[2], a[1]]
        assert pool.num_evictable == 1
        pool.check_invariants()

    def test_fork_revives_evictable(self):
        pool = self._pool()
        a = pool.alloc(2)
        pool.free(a)
        assert pool.is_evictable(a[0]) and pool.is_evictable(a[1])
        table = pool.fork(a)            # cache hit on parked blocks
        assert pool.num_evictable == 0 and pool.num_allocated == 2
        pool.check_invariants([table])
        pool.free(table)
        assert pool.num_evictable == 2
        pool.check_invariants([])

    def test_filter_selects_which_blocks_park(self):
        pool = self._pool()
        a = pool.alloc(4)
        indexed = {a[1], a[3]}
        pool.evictable_filter = indexed.__contains__
        pool.free(a)
        assert pool.num_evictable == 2 and pool.num_free == 5
        assert all(pool.is_evictable(b) for b in indexed)
        pool.check_invariants([])

    def test_exhaustion_counts_evictable_as_capacity(self):
        pool = self._pool()
        a = pool.alloc(7)
        pool.free(a[:3])                # 3 evictable, 4 still held
        assert pool.num_allocatable == 3 and pool.can_alloc(3)
        with pytest.raises(PoolExhausted):
            pool.alloc(4)               # beyond free + evictable
        assert pool.num_evictable == 3, "failed alloc must reclaim nothing"
        got = pool.alloc(3)             # exactly the cached pages
        assert set(got) == set(a[:3])
        pool.check_invariants()

    def test_purge_evictable(self):
        pool = self._pool()
        dropped = []
        pool.reclaim_hook = dropped.extend
        a = pool.alloc(3)
        pool.free(a)
        assert sorted(pool.purge_evictable()) == sorted(a)
        assert sorted(dropped) == sorted(a)
        assert pool.num_evictable == 0 and pool.num_free == 7
        pool.check_invariants([])

    def test_invariants_catch_evictable_and_free(self):
        pool = self._pool()
        a = pool.alloc(2)
        pool.free(a)
        pool._free.append(a[0])         # corrupt: evictable AND free
        with pytest.raises(ValueError, match="evictable and free"):
            pool.check_invariants()

    def test_invariants_catch_evictable_with_refcount(self):
        pool = self._pool()
        a = pool.alloc(1)
        pool._evictable[a[0]] = None    # corrupt: allocated AND evictable
        with pytest.raises(ValueError, match="evictable and allocated"):
            pool.check_invariants()

    def test_invariants_catch_use_after_free(self):
        """A live table referencing an evictable block is use-after-free:
        a reclaim would hand that page to another request mid-decode."""
        pool = self._pool()
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(ValueError, match="use-after-free"):
            pool.check_invariants([a])


class TestPrefixCacheEngine:
    """End-to-end KV reuse on the tiny model: cache-on must be token-exact
    vs cache-off while measurably skipping prefill compute."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _shared_prompts(self, n=4, prefix_len=12, tail_len=5, seed=0):
        rng = np.random.default_rng(seed)
        prefix = rng.integers(0, 128, prefix_len).astype(np.int32)
        return [np.concatenate([prefix,
                                rng.integers(0, 128, tail_len)
                                .astype(np.int32)]) for _ in range(n)]

    def _run(self, model, params, prompts, max_new=8, stagger=0, **kw):
        merged = dict(self.KW)
        merged.update(kw)
        eng = InferenceEngine(model, params, **merged)
        rids = []
        for i, p in enumerate(prompts):
            rids.append(eng.submit(p, max_new))
            if stagger and i % stagger == stagger - 1:
                eng.step()
        out = eng.run_until_complete()
        return eng, [out[r] for r in rids]

    def test_cache_on_equals_cache_off_staggered(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._shared_prompts()
        eng_on, on = self._run(model, params, prompts, stagger=1)
        eng_off, off = self._run(model, params, prompts, stagger=1,
                                 prefix_cache=False)
        assert on == off
        assert eng_off.prefix_cache is None
        assert eng_on.metrics.prefill_tokens_saved > 0, "cache never hit"
        assert eng_off.metrics.prefill_tokens_saved == 0
        s = eng_on.metrics.summary()
        assert s["prefix_hit_rate"] > 0
        assert s["prefill_tokens_saved"] == \
            eng_on.metrics.prefill_tokens_saved
        for p, toks in zip(prompts, on):
            assert toks == _greedy_ref(model, params, p, 8, eng_on.assembly_len)
        _assert_drained(eng_on)
        _assert_drained(eng_off)

    def test_cache_on_equals_cache_off_llama(self, llama_lm):
        """Same A/B over the other family's block: forked tables of grouped
        KV heads must read identically through the ragged paged-attention
        kernel, and rotary positions must survive a prefix hit."""
        model, params = llama_lm
        prompts = self._shared_prompts(seed=1)
        eng_on, on = self._run(model, params, prompts, stagger=1)
        eng_off, off = self._run(model, params, prompts, stagger=1,
                                 prefix_cache=False)
        assert on == off
        assert eng_on.metrics.prefill_tokens_saved > 0, "cache never hit"
        for p, toks in zip(prompts, on):
            assert toks == _greedy_ref(model, params, p, 8,
                                       eng_on.assembly_len)
        _assert_drained(eng_on)
        _assert_drained(eng_off)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_cache_on_equals_cache_off_under_preemption(self, lm, family):
        """A pool too small for the shared-prefix batch: preemption churns
        tables through free -> evictable -> revived, and outputs must stay
        token-exact against cache-off AND the offline reference."""
        model, params = lm
        prompts = self._shared_prompts(seed=2)
        kw = dict(num_blocks=9, block_size=4, max_batch_size=4,
                  max_seq_len=32)
        eng_on, on = self._run(model, params, prompts, **kw)
        eng_off, off = self._run(model, params, prompts,
                                 prefix_cache=False, **kw)
        assert eng_on.metrics.preemptions > 0, "pool was never exhausted"
        assert on == off
        for p, toks in zip(prompts, on):
            assert toks == _greedy_ref(model, params, p, 8, eng_on.assembly_len)
        _assert_drained(eng_on)
        _assert_drained(eng_off)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_cow_at_partial_block_boundary(self, lm, family):
        """Resubmitting an identical prompt is a FULL-COVER hit: every full
        block matches, so the matcher's first KV write (its recomputed last
        token) would land inside the last matched block. The engine must
        give it a private copy — and the published original must survive
        intact for the next twin."""
        model, params = lm
        p = np.arange(8, dtype=np.int32)   # exactly 2 full blocks
        eng = InferenceEngine(model, params, **self.KW)
        ref = _greedy_ref(model, params, p, 8, eng.assembly_len)
        r0 = eng.submit(p, 8)
        assert eng.run_until_complete()[r0] == ref
        assert eng.metrics.prefix_cows == 0
        r1 = eng.submit(p, 8)
        assert eng.run_until_complete()[r1] == ref
        assert eng.metrics.prefix_cows == 1
        assert eng.metrics.prefill_tokens_saved == 7  # all but the last token
        r2 = eng.submit(p, 8)              # the COW copy stayed private:
        assert eng.run_until_complete()[r2] == ref
        assert eng.metrics.prefix_cows == 2
        assert eng.metrics.prefill_tokens_saved == 14
        _assert_drained(eng)

    def test_eviction_under_pressure(self, tiny_lm):
        """Distinct prompts through a small pool: cached blocks must be
        reclaimed (LRU) to serve fresh allocations — the cache never
        reduces usable capacity and never leaks."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, num_blocks=9, block_size=4,
                              max_batch_size=2, max_seq_len=32)
        dropped = []
        inner = eng.pool.reclaim_hook
        eng.pool.reclaim_hook = lambda bs: (dropped.extend(bs), inner(bs))
        rng = np.random.default_rng(5)
        for _ in range(4):
            p = rng.integers(0, 128, 12).astype(np.int32)
            rid = eng.submit(p, 6)
            out = eng.run_until_complete()
            assert out[rid] == _greedy_ref(model, params, p, 6,
                                           eng.assembly_len)
            eng.check_invariants()
        assert dropped, "pool pressure never evicted a cached block"
        assert len(eng.prefix_cache) <= eng.pool.capacity
        _assert_drained(eng)

    def test_min_hit_blocks_suppresses_short_hits(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._shared_prompts(n=2, prefix_len=8, tail_len=5, seed=3)
        eng, out = self._run(model, params, prompts, stagger=1,
                             prefix_cache_min_hit_blocks=3)
        assert eng.metrics.prefill_tokens_saved == 0  # 2-block prefix < 3
        assert eng.metrics.prefix_hits == 0
        for p, toks in zip(prompts, out):
            assert toks == _greedy_ref(model, params, p, 8, eng.assembly_len)
        _assert_drained(eng)

    def test_stats_gauges(self, tiny_lm):
        model, params = tiny_lm
        prompts = self._shared_prompts(seed=4)
        eng_on, _ = self._run(model, params, prompts, stagger=1)
        s = eng_on.stats()
        assert s["prefix_cache_enabled"]
        assert s["prefix_indexed_blocks"] == len(eng_on.prefix_cache) > 0
        assert s["pool_evictable_blocks"] == eng_on.pool.num_evictable > 0
        eng_off, _ = self._run(model, params, prompts, prefix_cache=False)
        s = eng_off.stats()
        assert not s["prefix_cache_enabled"]
        assert s["prefix_indexed_blocks"] == 0
        assert s["pool_evictable_blocks"] == 0

    def test_chaos_gate_shared_prefix(self, tiny_lm):
        """The chaos gate re-run over a shared-prefix workload: alloc faults
        and a poisoned decode row while publish/fork/COW/evict churn the
        index. Every request terminal, survivors token-identical to the
        fault-free run, zero leaked blocks, partition invariants clean."""
        model, params = tiny_lm
        rng = np.random.default_rng(11)
        prefix = rng.integers(0, 128, 8).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(0, 128, int(t))
                                   .astype(np.int32)])
                   for t in rng.integers(2, 8, 8)]
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32)

        def run(plan=None):
            eng = InferenceEngine(model, params, faults=plan, **kw)
            rids = [eng.submit(p, 8) for p in prompts]
            eng.run_until_complete()
            return eng, rids

        ref_eng, ref_rids = run()
        assert ref_eng.metrics.prefill_tokens_saved > 0, \
            "workload never exercised the cache — dead test"
        plan = FaultPlan(seed=21, alloc_fail_prob=0.12, nan_logit_calls=(5,))
        eng, rids = run(plan)
        assert plan.fired["pool.alloc"] >= 1, "chaos never fired — dead test"
        states = [eng.result(r).state for r in rids]
        assert all(s in TERMINAL_STATES for s in states)
        assert RequestState.FINISHED in states, "no request survived"
        out, ref = _finished(eng), _finished(ref_eng)
        for rid, ref_rid in zip(rids, ref_rids):
            if rid in out:
                assert out[rid] == ref[ref_rid], f"survivor {rid} diverged"
        _assert_drained(eng)
        _assert_drained(ref_eng)


@pytest.mark.slow
def test_gpt2_small_prefix_cache_matches_uncached():
    """Cache-on vs cache-off A/B on gpt2_small with chunk boundaries aligned
    to the cached prefix (prefix = 1 block = 1 chunk): the sharers' uncached
    tail chunk starts at the same position with the same width in both runs,
    so the compiled programs match and exact token equality is well-posed
    (the cached KV is bit-identical to what a recompute would produce — it
    IS the publisher's pages)."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, model.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, model.vocab_size, 8)
                               .astype(np.int32)]) for _ in range(4)]

    def run(cache):
        eng = InferenceEngine(model, params, num_blocks=32, block_size=16,
                              max_batch_size=4, max_seq_len=48,
                              chunk_size=16, prefix_cache=cache)
        rids = [eng.submit(prompts[0], 8)]
        eng.step(); eng.step()      # r0's two chunks land; prefix published
        rids += [eng.submit(p, 8) for p in prompts[1:]]
        out = eng.run_until_complete()
        return eng, [out[r] for r in rids]

    eng_on, on = run(True)
    eng_off, off = run(False)
    assert on == off
    assert eng_on.metrics.prefill_tokens_saved == 16 * 3  # one block each
    assert eng_off.metrics.prefill_tokens_saved == 0
    _assert_drained(eng_on)
    _assert_drained(eng_off)


# -- supervised runtime -------------------------------------------------------


class TestSupervisor:
    """The resilience layer above the engine: graceful drain, crash
    recovery with a bounded restart budget, step-latency watchdog,
    disconnect-cancel, overload shedding — all driven synchronously
    (``run_sync``/``pump``) so every schedule is deterministic."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _sup(self, tiny_lm, plan=None, *, engine_kw=None, **kw):
        model, params = tiny_lm
        ekw = dict(self.KW)
        ekw.update(engine_kw or {})
        eng = InferenceEngine(model, params, faults=plan, **ekw)
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               restart_backoff_s=0.0, **kw)
        return sup, eng, events

    @staticmethod
    def _terminals(events):
        return [e for e in events if e["event"] != "token"]

    def test_graceful_drain_finishes_inflight(self, tiny_lm):
        model, params = tiny_lm
        sup, eng, events = self._sup(tiny_lm)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 128, n).astype(np.int32) for n in (5, 7, 4)]
        refs = [_greedy_ref(model, params, p, 6, eng.assembly_len)
                for p in prompts]
        rids = [sup.submit(p, 6) for p in prompts]
        sup.pump(2)                           # work now genuinely in flight
        sup.request_drain("test drain")
        assert sup.draining
        with pytest.raises(ShuttingDown, match="draining"):
            sup.submit(prompts[0], 2)
        sup.run_sync()
        assert sup.state is SupervisorState.STOPPED
        assert sup.exit_code == 0
        assert sup.drain_duration_s is not None
        assert eng.metrics.summary()["drain_duration_s"] == \
            sup.drain_duration_s
        done = {e["id"]: e for e in events if e["event"] == "done"}
        assert sorted(done) == sorted(rids)
        assert len(self._terminals(events)) == len(rids)  # exactly one each
        for rid, ref in zip(rids, refs):
            assert done[rid]["tokens"] == ref
            assert done[rid]["ttft_ms"] >= 0
        with pytest.raises(ShuttingDown, match="stopped"):
            sup.submit(prompts[0], 2)
        _assert_drained(eng)

    def test_drain_deadline_times_out_stragglers(self, tiny_lm):
        plan = FaultPlan(step_delay_s=0.03)
        sup, eng, events = self._sup(tiny_lm, plan, drain_deadline_s=0.02)
        rids = [sup.submit(np.arange(5, dtype=np.int32) + i, 8)
                for i in range(2)]
        sup.pump(1)
        sup.request_drain("deadline test")
        sup.run_sync()
        assert sup.state is SupervisorState.STOPPED  # drain is still clean
        assert sup.exit_code == 0
        touts = [e for e in self._terminals(events) if e["event"] == "timeout"]
        assert touts, "no request hit the drain deadline"
        assert all("drain deadline" in e["reason"] for e in touts)
        assert len(self._terminals(events)) == len(rids)
        assert eng.pool.num_allocated == 0
        eng.check_invariants()

    def test_watchdog_trips_and_recovers(self, tiny_lm):
        """A wedged step (injected latency) is treated like a crash. The
        engine is warmed with the exact same shapes first so compile time
        never reaches the watchdog — only the injected delay does."""
        model, params = tiny_lm
        eng = InferenceEngine(model, params, **self.KW)
        warm = [np.arange(5, dtype=np.int32), np.arange(6, dtype=np.int32)]
        for p in warm:
            eng.submit(p, 4)
        eng.run_until_complete()
        eng.faults = FaultPlan(step_delay_s=0.2, step_delay_calls=(2,))
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               watchdog_step_s=0.05, max_restarts=2,
                               restart_backoff_s=0.0)
        refs = [_greedy_ref(model, params, p, 4, eng.assembly_len)
                for p in warm]
        rids = [sup.submit(p, 4) for p in warm]
        for _ in range(200):
            sup.pump(1)
            if sup.restarts:
                break
        # disarm for the recovery leg: the resumed requests re-prefill at
        # new lengths, and a compile there must not count as a wedge (same
        # caveat as the fresh-request leg below)
        sup.watchdog_step_s = None
        sup.run_sync()
        assert sup.restarts == 1
        assert sup.state is SupervisorState.RUNNING   # recovered, not dead
        term = {e["id"]: e for e in self._terminals(events)}
        assert sorted(term) == sorted(rids)
        # the wedged step cost the requests their KV, not their lives: both
        # migrate through the resume path and finish token-exact
        for rid, ref in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == ref
        assert eng.metrics.migrated_requests == 2
        assert eng.metrics.summary()["engine_restarts"] == 1
        assert eng.pool.num_allocated == 0
        eng.check_invariants()
        # the recovered engine still serves: a fresh request completes
        # (watchdog off for this leg — a solo request hits decode buckets
        # the warmup never compiled, and compiles must not count as wedges)
        sup.watchdog_step_s = None
        eng.faults = None
        ref = _greedy_ref(model, params, warm[0], 4, eng.assembly_len)
        rid = sup.submit(warm[0], 4)
        sup.run_sync()
        done = [e for e in events if e["event"] == "done" and e["id"] == rid]
        assert len(done) == 1 and done[0]["tokens"] == ref

    def test_engine_crash_restart_resumes_inflight(self, tiny_lm):
        """A crash no longer fails in-flight work: RUNNING requests lose
        their KV pages but keep their committed tokens, migrate through
        the recompute-resume path on restart, and finish token-exact —
        indistinguishable (to the client stream) from an uninterrupted
        run. QUEUED requests survive as before."""
        model, params = tiny_lm
        plan = FaultPlan(step_crash_calls=(2,))
        sup, eng, events = self._sup(tiny_lm, plan, max_restarts=2,
                                     engine_kw=dict(max_batch_size=2))
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 5, eng.assembly_len)
                for p in prompts]
        rids = [sup.submit(p, 5) for p in prompts]
        sup.run_sync()
        assert sup.restarts == 1
        term = {e["id"]: e for e in self._terminals(events)}
        assert sorted(term) == sorted(rids)
        assert eng.metrics.migrated_requests == 2   # the in-flight batch
        for rid, ref in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == ref
            # the client stream never saw a duplicated or dropped token
            streamed = [e["token"] for e in events
                        if e["event"] == "token" and e["id"] == rid]
            assert streamed == ref
        _assert_drained(eng)

    def test_restart_budget_exhaustion_fails_everything(self, tiny_lm):
        plan = FaultPlan(step_crash_calls=(1, 2, 3))
        sup, eng, events = self._sup(tiny_lm, plan, max_restarts=2)
        rids = [sup.submit(np.arange(4, dtype=np.int32) + i, 4)
                for i in range(2)]
        sup.run_sync()
        assert sup.state is SupervisorState.FAILED
        assert sup.exit_code == 1
        assert sup.restarts == 3          # two recoveries + the fatal one
        term = {e["id"]: e for e in self._terminals(events)}
        assert sorted(term) == sorted(rids)
        assert all(e["event"] == "error" and
                   "restart budget exhausted (2)" in e["reason"]
                   for e in term.values())
        with pytest.raises(ShuttingDown, match="failed"):
            sup.submit(np.arange(4, dtype=np.int32), 2)
        assert eng.pool.num_allocated == 0
        eng.check_invariants()

    def test_migration_budget_exhaustion_fails_poison(self, tiny_lm):
        """A request that keeps crashing its engine is FAILED with a
        structured reason once its migration budget is spent — poison
        isolation, so one bad request cannot wedge the restart loop.
        The supervisor stays RUNNING and keeps serving."""
        model, params = tiny_lm
        # crashes spaced so the victim is re-admitted (RUNNING, charged a
        # migration) before each one — back-to-back crashes would only ever
        # see it QUEUED
        plan = FaultPlan(step_crash_calls=(2, 4, 6))
        sup, eng, events = self._sup(tiny_lm, plan, max_restarts=10,
                                     engine_kw=dict(migration_budget=2))
        sup.submit(np.arange(1, 6, dtype=np.int32), 8)
        sup.run_sync()
        term = self._terminals(events)
        assert len(term) == 1 and term[0]["event"] == "error"
        assert "migration budget exhausted (2)" in term[0]["reason"]
        assert sup.state is SupervisorState.RUNNING
        assert eng.metrics.migrated_requests == 2
        # the engine still serves a fresh request token-exact
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 4, eng.assembly_len)
        rid2 = sup.submit(p, 4)
        sup.run_sync()
        done = [e for e in events
                if e["event"] == "done" and e["id"] == rid2]
        assert len(done) == 1 and done[0]["tokens"] == ref
        _assert_drained(eng)

    def test_restart_backoff_interruptible_by_drain(self, tiny_lm):
        """The restart backoff must not block shutdown: a drain arriving
        mid-backoff wakes the worker immediately instead of letting the
        process hang for the remaining (possibly seconds-long) sleep."""
        model, params = tiny_lm
        plan = FaultPlan(step_crash_calls=(1,))
        eng = InferenceEngine(model, params, faults=plan, **self.KW)
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               max_restarts=2, restart_backoff_s=30.0,
                               restart_backoff_max_s=30.0).start()
        rid = sup.submit(np.arange(5, dtype=np.int32), 4)
        deadline = time.monotonic() + 10.0
        while sup.restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sup.restarts == 1, "crash never landed"
        sup.request_drain("test drain")       # worker is in its backoff
        assert sup.join(timeout=10.0), \
            "drain blocked behind the restart backoff sleep"
        assert sup.state is SupervisorState.STOPPED
        assert sup.exit_code == 0
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[rid]["event"] == "done"
        _assert_drained(eng)

    def test_client_disconnect_cancels_request(self, tiny_lm):
        """A front end consulting plan.client_disconnect() drops a client
        mid-stream; cancelling from inside the listener (the sweep's
        dispatch) must be re-entrant and emit exactly one terminal."""
        model, params = tiny_lm
        plan = FaultPlan(client_disconnect_calls=(2,))
        sup, eng, events = self._sup(tiny_lm)
        p0, p1 = np.arange(5, dtype=np.int32), np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p1, 6, eng.assembly_len)

        def flaky_listener(ev):
            if ev["event"] == "token" and plan.client_disconnect():
                sup.cancel(ev["id"], "client disconnected mid-stream")

        r0 = sup.submit(p0, 6, listener=flaky_listener)
        r1 = sup.submit(p1, 6)
        sup.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[r0]["event"] == "cancelled"
        assert "client disconnected" in term[r0]["reason"]
        assert term[r1]["event"] == "done" and term[r1]["tokens"] == ref
        assert len(self._terminals(events)) == 2
        assert plan.fired["client.disconnect"] == 1
        _assert_drained(eng)

    def test_priority_shed_under_overload(self, tiny_lm):
        """Backpressure degrades background traffic first: a full queue
        sheds its least-important (largest priority value, newest) member
        for a more-important arrival; equal priority still rejects."""
        sup, eng, events = self._sup(
            tiny_lm, engine_kw=dict(max_queue_depth=2))
        p = np.arange(5, dtype=np.int32)
        bg1 = sup.submit(p, 4, priority=5)
        bg2 = sup.submit(p + 1, 4, priority=5)
        fg = sup.submit(p + 2, 4, priority=0)     # sheds bg2 (newest bg)
        with pytest.raises(AdmissionRejected):
            sup.submit(p + 3, 4, priority=5)      # no one less important
        sup.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[bg2]["event"] == "error"
        assert "shed under overload" in term[bg2]["reason"]
        assert "priority 5" in term[bg2]["reason"]
        assert term[bg1]["event"] == "done"
        assert term[fg]["event"] == "done"
        s = sup.stats()
        assert s["shed_requests"] == 1
        assert s["rejected"] == 1
        assert s["supervisor_state"] == "running"
        _assert_drained(eng)

    def test_threaded_submit_stats_and_drain(self, tiny_lm):
        """The worker-thread path: submits/stats marshalled through the
        command queue, drain from another thread, clean join."""
        model, params = tiny_lm
        sup, eng, events = self._sup(tiny_lm)
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, eng.assembly_len)
        sup.start()
        import queue as _q
        got: "_q.Queue[dict]" = _q.Queue()
        rid = sup.submit(p, 5, listener=got.put)
        ev = got.get(timeout=60)
        seen = [ev]
        while ev["event"] == "token":
            ev = got.get(timeout=60)
            seen.append(ev)
        assert ev["event"] == "done" and ev["tokens"] == ref
        assert [e["token"] for e in seen[:-1]] == ref
        assert sup.stats()["supervisor_state"] == "running"
        sup.request_drain("test over")
        assert sup.join(timeout=30)
        assert sup.state is SupervisorState.STOPPED
        assert sup.exit_code == 0
        with pytest.raises(ShuttingDown):
            sup.submit(p, 2)
        assert rid in {e["id"] for e in events}
        _assert_drained(eng)


class TestCrashResumeExactness:
    """The in-flight crash-survival contract, exhaustively: an engine
    crash at ANY point in a request's life — mid-prefill-chunk,
    mid-decode, mid-spec-draft — loses KV pages but never committed
    tokens. After the supervisor restart, every request migrates through
    the recompute-resume path and both the final output and the streamed
    token sequence are byte-identical to an uninterrupted run (the offline
    reference), across model families and with the prefix cache on or
    off."""

    @pytest.mark.parametrize("cache", [True, False],
                             ids=["cache", "nocache"])
    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize(
        "site", ["prefill_chunk", "decode", "spec_draft"])
    def test_crash_resume_token_exact(self, lm, site, family, cache):
        model, params = lm
        kw = dict(num_blocks=32, block_size=4, max_batch_size=4,
                  max_seq_len=32, prefix_cache=cache)
        if site == "spec_draft":
            kw.update(spec="ngram", spec_k=3)
            prompts = _cyclic_prompts(2, seed=3)
            crash_at = (4,)   # decode steps have drafts in flight
        elif site == "prefill_chunk":
            kw.update(chunk_size=4)
            rng = np.random.default_rng(1)
            prompts = [rng.integers(0, 128, n).astype(np.int32)
                       for n in (10, 9)]
            crash_at = (2,)   # first chunk landed; prompts mid-prefill
        else:
            rng = np.random.default_rng(2)
            prompts = [rng.integers(0, 128, n).astype(np.int32)
                       for n in (5, 7)]
            crash_at = (4,)   # several decode tokens already committed
        max_new = 6
        plan = FaultPlan(step_crash_calls=crash_at)
        eng = InferenceEngine(model, params, faults=plan, **kw)
        refs = [_greedy_ref(model, params, p, max_new, eng.assembly_len)
                for p in prompts]
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               restart_backoff_s=0.0, max_restarts=2)
        rids = [sup.submit(p, max_new) for p in prompts]
        sup.run_sync()
        assert sup.restarts == 1
        assert plan.fired["engine.step"] == 1
        assert eng.metrics.migrated_requests >= 1
        term = {e["id"]: e for e in events if e["event"] != "token"}
        assert sorted(term) == sorted(rids)
        for rid, ref in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == ref
            streamed = [e["token"] for e in events
                        if e["event"] == "token" and e["id"] == rid]
            assert streamed == ref    # no token duplicated or dropped
        _assert_drained(eng)


class TestDegradation:
    """Overload degradation at the engine level: prefix-cache publish
    suspension under pool pressure (shedding is covered above)."""

    def test_publish_suspension_under_pool_pressure(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, 128, 8).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.integers(0, 128, 4).astype(np.int32)])
                   for _ in range(3)]
        # threshold 0.0: any live allocation counts as pressure, so every
        # publish is suspended and the index never grows
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              prefix_publish_max_occupancy=0.0)
        rids = [eng.submit(p, 4) for p in prompts]
        out = eng.run_until_complete()
        s = eng.stats()
        assert s["prefix_indexed_blocks"] == 0
        assert s["publish_suspended"] > 0
        assert s["prefix_hits"] == 0
        assert all(eng.result(r).state is RequestState.FINISHED
                   for r in rids)
        for r, p in zip(rids, prompts):
            assert out[r] == _greedy_ref(model, params, p, 4,
                                         eng.assembly_len)
        _assert_drained(eng)

    def test_default_threshold_publishes_normally(self, tiny_lm):
        model, params = tiny_lm
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, 128, 8).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.integers(0, 128, 4).astype(np.int32)])
                   for _ in range(3)]
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32)
        for p in prompts:
            eng.submit(p, 4)
        eng.run_until_complete()
        s = eng.stats()
        assert s["prefix_indexed_blocks"] > 0
        assert s["publish_suspended"] == 0
        _assert_drained(eng)


@pytest.mark.slow
def test_chaos_soak_supervised(tiny_lm):
    """The soak gate: hundreds of staggered requests through a supervised
    engine with chaos on — alloc faults, NaN rows, client disconnects, and
    one injected engine-loop crash. Asserts the full resilience contract:
    every request reaches exactly one terminal event, the supervisor
    recovers from the crash (restarts == 1) and drains cleanly, zero
    leaked blocks, and fault-free survivors are token-identical to the
    offline greedy reference."""
    model, params = tiny_lm
    rng = np.random.default_rng(42)
    uniq = [rng.integers(0, 128, int(n)).astype(np.int32)
            for n in rng.integers(4, 14, 8)]
    max_new = 6
    eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                          max_batch_size=4, max_seq_len=32,
                          max_queue_depth=24)
    refs = {i: _greedy_ref(model, params, p, max_new, eng.assembly_len)
            for i, p in enumerate(uniq)}
    plan = FaultPlan(seed=7, alloc_fail_prob=0.02, nan_logit_prob=0.01,
                     client_disconnect_prob=0.04, step_crash_calls=(60,))
    eng.faults = plan
    eng.pool.fault_plan = plan
    events = []
    sup = EngineSupervisor(eng, event_sink=events.append, max_restarts=3,
                           restart_backoff_s=0.0, drain_deadline_s=60.0)

    def flaky_listener(ev):
        if ev["event"] == "token" and plan.client_disconnect():
            sup.cancel(ev["id"], "client disconnected mid-stream")

    n_requests, rejected, submitted = 200, 0, {}
    for i in range(n_requests):
        which = int(rng.integers(0, len(uniq)))
        try:
            rid = sup.submit(uniq[which], max_new, priority=i % 3,
                             listener=flaky_listener)
            submitted[rid] = which
        except AdmissionRejected:
            rejected += 1
        sup.pump(1)                        # staggered: interleave with steps
    sup.run_sync()
    sup.request_drain("soak complete")
    sup.run_sync()

    # lifecycle: clean drain despite the injected crash
    assert sup.state is SupervisorState.STOPPED
    assert sup.exit_code == 0
    assert sup.restarts == 1, f"expected exactly one restart: {sup.restarts}"
    # every fault site actually exercised
    assert plan.fired["engine.step"] == 1
    assert plan.fired["pool.alloc"] > 0
    assert plan.fired["decode.logits"] > 0
    assert plan.fired["client.disconnect"] > 0
    assert rejected + len(submitted) == n_requests
    # exactly one terminal event per admitted request
    terminals = [e for e in events if e["event"] != "token"]
    per_rid = {}
    for e in terminals:
        per_rid[e["id"]] = per_rid.get(e["id"], 0) + 1
    assert sorted(per_rid) == sorted(submitted)
    assert all(c == 1 for c in per_rid.values()), per_rid
    states = {rid: eng.result(rid).state for rid in submitted}
    assert all(st in TERMINAL_STATES for st in states.values())
    # zero leaks after crash recovery + drain
    assert eng.pool.num_allocated == 0
    eng.check_invariants()
    # survivors are token-exact against the fault-free reference
    finished = [e for e in terminals if e["event"] == "done"]
    assert finished, "soak finished nothing"
    for e in finished:
        assert e["tokens"] == refs[submitted[e["id"]]], \
            f"rid {e['id']} diverged from fault-free reference"
    s = eng.stats()
    assert s["engine_restarts"] == 1
    assert s["drain_duration_s"] >= 0.0


# -- replicated failover router -----------------------------------------------


class TestCircuitBreaker:
    """Pure state-machine tests: CLOSED → OPEN on consecutive failures,
    OPEN → HALF_OPEN after cooldown, one probe decides re-CLOSE/re-OPEN."""

    def test_opens_after_consecutive_failures(self):
        b = CircuitBreaker(threshold=3, cooldown_s=60.0)
        assert b.state is BreakerState.CLOSED
        b.record_failure()
        b.record_failure()
        assert b.state is BreakerState.CLOSED and b.allows()
        b.record_failure()
        assert b.state is BreakerState.OPEN and not b.allows()

    def test_success_resets_consecutive_count(self):
        b = CircuitBreaker(threshold=2, cooldown_s=60.0)
        b.record_failure()
        b.record_success()
        b.record_failure()          # not consecutive: stays closed
        assert b.state is BreakerState.CLOSED and b.allows()

    def test_half_open_probe_success_recloses(self):
        b = CircuitBreaker(threshold=1, cooldown_s=0.0)
        b.record_failure()
        assert b.state is BreakerState.OPEN
        assert b.allows()                     # cooldown 0: probe admitted
        assert b.state is BreakerState.HALF_OPEN
        b.on_dispatch()
        assert not b.allows()                 # a single probe at a time
        b.record_success()
        assert b.state is BreakerState.CLOSED and b.allows()

    def test_half_open_probe_failure_reopens(self):
        b = CircuitBreaker(threshold=1, cooldown_s=0.0)
        b.trip()
        assert b.allows()
        b.on_dispatch()
        b.record_failure()                    # the probe failed
        assert b.state is BreakerState.OPEN

    def test_stale_success_cannot_close_open_breaker(self):
        """Regression: a success recorded while the breaker is OPEN (a
        stream that dispatched before the trip landing its terminal after
        it) must NOT close the breaker — only a HALF_OPEN probe or normal
        CLOSED traffic counts."""
        b = CircuitBreaker(threshold=1, cooldown_s=60.0)
        b.trip()
        assert b.state is BreakerState.OPEN
        b.record_success()                    # stale: from before the trip
        assert b.state is BreakerState.OPEN and not b.allows()

    def test_stale_success_race_after_probe_failure(self):
        """The precise race: probe admitted, probe fails (re-OPEN), THEN a
        stale success from an older stream arrives. The breaker must stay
        OPEN — otherwise one laggard ack reopens the floodgates onto a
        replica the probe just proved dead."""
        b = CircuitBreaker(threshold=1, cooldown_s=0.0)
        b.record_failure()
        assert b.allows()                     # cooldown 0: probe admitted
        b.on_dispatch()
        b.record_failure()                    # probe failed: re-OPEN
        assert b.state is BreakerState.OPEN
        b.record_success()                    # stale ack from an old stream
        assert b.state is BreakerState.OPEN


class TestHealthScore:
    """Unit tests for the EWMA health score: the healthy fixed point is
    exactly 1.0 (so a fresh fleet places as pure JSQ), and each signal
    contributes its documented weight."""

    def test_fresh_score_is_exactly_one(self):
        from tnn_tpu.serving import HealthScore

        hs = HealthScore()
        assert hs.score() == 1.0
        assert hs.samples == 0

    def test_dispatch_latency_ewma_blend(self):
        from tnn_tpu.serving import HealthScore

        hs = HealthScore()
        hs.observe_dispatch(1.0)
        assert hs.dispatch_latency_s == pytest.approx(HealthScore.ALPHA)
        assert hs.score() == pytest.approx(
            1.0 + HealthScore.W_DISPATCH * HealthScore.ALPHA)
        hs.observe_dispatch(1.0)
        a = HealthScore.ALPHA
        assert hs.dispatch_latency_s == pytest.approx((1 - a) * a + a)

    def test_gauge_sample_contributions(self):
        from tnn_tpu.serving import HealthScore

        hs = HealthScore()
        hs.observe_gauges(0.1, 4.0, 0.0)
        a = HealthScore.ALPHA
        assert hs.step_latency_s == pytest.approx(a * 0.1)
        assert hs.queue_depth == pytest.approx(a * 4.0)
        assert hs.score() == pytest.approx(
            1.0 + HealthScore.W_STEP * a * 0.1
            + HealthScore.W_QUEUE * a * 4.0)

    def test_error_rate_folds_and_decays(self):
        from tnn_tpu.serving import HealthScore

        hs = HealthScore()
        hs.observe_outcome(False)
        a = HealthScore.ALPHA
        assert hs.error_rate == pytest.approx(a)
        assert hs.score() == pytest.approx(1.0 + HealthScore.W_ERROR * a)
        hs.observe_outcome(True)              # success decays the EWMA
        assert hs.error_rate == pytest.approx((1 - a) * a)

    def test_staleness_grace_window(self):
        from tnn_tpu.serving import HealthScore

        hs = HealthScore()
        # inside the grace window: free — probe cadence jitter is normal
        hs.observe_gauges(0.0, 0.0, HealthScore.STALE_GRACE_S * 0.5)
        assert hs.score() == 1.0
        # past it: a wedged-but-responsive worker starts paying
        hs.observe_gauges(0.0, 0.0, HealthScore.STALE_GRACE_S + 2.0)
        assert hs.score() == pytest.approx(1.0 + HealthScore.W_STALE * 2.0)


class TestFaultPlanGraySites:
    """Seed-determinism and semantics of the gray-failure fault sites:
    replica.slow, net.partition (windowed), net.flaky (per-replica)."""

    def test_replica_slow_seed_deterministic(self):
        a = FaultPlan(seed=11, replica_slow_prob=0.3)
        b = FaultPlan(seed=11, replica_slow_prob=0.3)
        trace_a = [a.replica_slow() for _ in range(50)]
        trace_b = [b.replica_slow() for _ in range(50)]
        assert trace_a == trace_b
        assert any(trace_a) and not all(trace_a)
        assert a.fired["replica.slow"] == sum(trace_a)
        # a different seed yields a different schedule
        c = FaultPlan(seed=12, replica_slow_prob=0.3)
        assert [c.replica_slow() for _ in range(50)] != trace_a

    def test_replica_slow_scheduled_calls(self):
        p = FaultPlan(replica_slow_calls=(3,))
        assert [p.replica_slow() for _ in range(5)] == \
            [False, False, True, False, False]
        assert p.fired["replica.slow"] == 1

    def test_partition_window_semantics(self):
        """One hit opens a window of net_partition_rounds consults; every
        consult inside it reports active, then the window closes."""
        p = FaultPlan(net_partition_calls=(2,), net_partition_rounds=3)
        got = [p.net_partition() for _ in range(7)]
        assert got == [False, True, True, True, False, False, False]
        assert p.fired["net.partition"] == 1   # one HIT, one window

    def test_partition_active_is_a_pure_read(self):
        """partition_active never advances the rng stream: two identical
        plans, one read between every consult, fire identically."""
        a = FaultPlan(seed=7, net_partition_prob=0.2,
                      net_partition_rounds=2)
        b = FaultPlan(seed=7, net_partition_prob=0.2,
                      net_partition_rounds=2)
        trace_a, trace_b = [], []
        for _ in range(40):
            trace_a.append(a.net_partition())
            trace_b.append(b.net_partition())
            for _ in range(5):                 # hammer the pure read
                b.partition_active
        assert trace_a == trace_b
        assert a.fired["net.partition"] == b.fired["net.partition"] > 0

    def test_partition_active_tracks_window(self):
        p = FaultPlan(net_partition_calls=(1,), net_partition_rounds=2)
        assert not p.partition_active
        assert p.net_partition()               # hit: window opens
        assert p.partition_active              # one consult left
        assert p.net_partition()               # last consult of the window
        assert not p.partition_active
        assert not p.net_partition()

    def test_flaky_drop_only_consults_configured_replica(self):
        """Calls to healthy replicas never perturb the flaky schedule —
        the rng stream depends only on the flaky replica's own calls."""
        p = FaultPlan(flaky_replica=1, flaky_drop_calls=(1,))
        assert not p.flaky_drop(0)             # wrong replica: no consult
        assert p.calls["net.flaky"] == 0
        assert p.flaky_drop(1)                 # 1st consult = scheduled hit
        assert not p.flaky_drop(1)
        assert p.fired["net.flaky"] == 1
        # disabled site never consults at all
        q = FaultPlan(flaky_drop_prob=1.0)     # flaky_replica defaults -1
        assert not q.flaky_drop(0) and q.calls["net.flaky"] == 0

    def test_flaky_drop_seed_deterministic(self):
        a = FaultPlan(seed=5, flaky_replica=2, flaky_drop_prob=0.4)
        b = FaultPlan(seed=5, flaky_replica=2, flaky_drop_prob=0.4)
        # interleave irrelevant-replica calls on one plan only
        trace_a = [a.flaky_drop(2) for _ in range(40)]
        trace_b = []
        for _ in range(40):
            b.flaky_drop(0)
            trace_b.append(b.flaky_drop(2))
            b.flaky_drop(1)
        assert trace_a == trace_b
        assert any(trace_a) and not all(trace_a)


class TestRouter:
    """The failover front end over N supervised replicas, driven through
    the deterministic sync harness (``pump``/``run_sync``): placement,
    retries, mid-stream migration, breaker integration, cascade drain."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _router(self, tiny_lm, n=3, *, plans=None, router_kw=None,
                engine_kw=None, sup_kw=None):
        model, params = tiny_lm
        ekw = dict(self.KW)
        ekw.update(engine_kw or {})
        skw = dict(restart_backoff_s=0.0)
        skw.update(sup_kw or {})
        plans = plans or [None] * n
        sups = [EngineSupervisor(
                    InferenceEngine(model, params, faults=plans[i], **ekw),
                    **skw)
                for i in range(n)]
        events = []
        router = Router(sups, event_sink=events.append, seed=0,
                        **(router_kw or {}))
        return router, sups, events

    @staticmethod
    def _terminals(events):
        return [e for e in events if e["event"] != "token"]

    def test_jsq_placement_spreads_load(self, tiny_lm):
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=2)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 5,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 5) for p in prompts]
        # join-shortest-queue: 4 submits over 2 replicas → 2 each
        assert [len(h.live) for h in router.replicas] == [2, 2]
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        for gid, ref in zip(gids, refs):
            assert term[gid]["event"] == "done"
            assert term[gid]["tokens"] == ref
        assert router.stats()["router_retries"] == 0

    def test_kill_replica_midstream_migrates_token_exact(self, tiny_lm):
        """The headline failover: a replica is hard-killed with requests
        mid-decode; its live streams re-dispatch to the survivors and the
        client sees an uninterrupted token-exact stream."""
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=3)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 8,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 8) for p in prompts]
        router.pump(3)                 # streams genuinely mid-flight
        victim = max(router.replicas, key=lambda h: len(h.live)).idx
        assert len(router.replicas[victim].live) > 0
        router.kill_replica(victim)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert sorted(term) == sorted(gids)
        for gid, ref in zip(gids, refs):
            assert term[gid]["event"] == "done"
            assert term[gid]["tokens"] == ref
            streamed = [e["token"] for e in events
                        if e["event"] == "token" and e["id"] == gid]
            assert streamed == ref     # no token duplicated or dropped
        assert router.metrics.migrated_requests > 0
        st = router.stats()
        assert st["replicas"][victim]["killed"]
        assert st["replicas"][victim]["breaker_state"] == "open"
        # survivors leak nothing
        for h in router.replicas:
            if h.idx != victim:
                assert h.sup.engine.pool.num_allocated == 0
                h.sup.engine.check_invariants()

    def test_replica_internal_restart_is_invisible(self, tiny_lm):
        """An engine crash INSIDE a replica is the supervisor's problem:
        it restarts, migrates its own requests, and the router never even
        sees an error — no router-level migration, just replica_restarts
        in the stats."""
        model, params = tiny_lm
        plans = [FaultPlan(step_crash_calls=(2,)), None]
        router, sups, events = self._router(tiny_lm, n=2, plans=plans)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 5,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 5) for p in prompts]
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        for gid, ref in zip(gids, refs):
            assert term[gid]["event"] == "done"
            assert term[gid]["tokens"] == ref
        assert router.metrics.migrated_requests == 0
        st = router.stats()
        assert st["replica_restarts"] == 1
        assert all(r["breaker_state"] == "closed" for r in st["replicas"])

    def test_restart_budget_exhaustion_fails_over(self, tiny_lm):
        """A replica that crashes until its supervisor gives up emits
        'restart budget exhausted' for its requests — a replica-level
        failure the router turns into migration, not client errors."""
        model, params = tiny_lm
        plans = [FaultPlan(step_crash_calls=(1, 2, 3, 4, 5, 6)), None]
        router, sups, events = self._router(
            tiny_lm, n=2, plans=plans, sup_kw=dict(max_restarts=1))
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6)]
        refs = [_greedy_ref(model, params, p, 5,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 5) for p in prompts]
        router.run_sync()
        assert sups[0].state is SupervisorState.FAILED
        term = {e["id"]: e for e in self._terminals(events)}
        for gid, ref in zip(gids, refs):
            assert term[gid]["event"] == "done"
            assert term[gid]["tokens"] == ref
        assert router.metrics.migrated_requests >= 1

    def test_router_migration_budget_exhausts_poison(self, tiny_lm):
        """migration_budget=0: the first failover attempt FAILs the
        request with a structured reason instead of bouncing it around
        the fleet forever."""
        router, sups, events = self._router(
            tiny_lm, n=2, router_kw=dict(migration_budget=0))
        gid = router.submit(np.arange(5, dtype=np.int32), 8)
        router.pump(2)
        victim = next(h.idx for h in router.replicas if h.live)
        router.kill_replica(victim)
        router.run_sync()
        term = self._terminals(events)
        assert len(term) == 1 and term[0]["event"] == "error"
        assert term[0]["id"] == gid
        assert "router migration budget exhausted (0)" in term[0]["reason"]

    def test_net_drop_retries_then_succeeds(self, tiny_lm):
        model, params = tiny_lm
        router, sups, events = self._router(
            tiny_lm, n=2,
            router_kw=dict(faults=FaultPlan(net_drop_calls=(1,)),
                           retry_backoff_s=0.0, retry_jitter_s=0.0))
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, sups[0].engine.assembly_len)
        gid = router.submit(p, 5)       # first call dropped, retry lands
        assert router.metrics.router_retries == 1
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        assert router.stats()["router_retries"] == 1

    def test_net_drop_exhausts_retries_and_raises(self, tiny_lm):
        router, sups, events = self._router(
            tiny_lm, n=2,
            router_kw=dict(faults=FaultPlan(net_drop_prob=1.0),
                           max_retries=2, retry_backoff_s=0.0,
                           retry_jitter_s=0.0))
        with pytest.raises(ConnectionError):
            router.submit(np.arange(5, dtype=np.int32), 4)
        assert router.metrics.router_retries == 2
        assert router.stats()["router_open_requests"] == 0

    def test_deadline_respected_during_retries(self, tiny_lm):
        """A retry whose backoff would overshoot the request deadline
        fails the request as a timeout instead of burning the budget."""
        router, sups, events = self._router(
            tiny_lm, n=2,
            router_kw=dict(faults=FaultPlan(net_drop_prob=1.0),
                           retry_backoff_s=5.0, retry_jitter_s=0.0))
        gid = router.submit(np.arange(5, dtype=np.int32), 4,
                            deadline_s=0.05)
        term = self._terminals(events)
        assert len(term) == 1 and term[0]["id"] == gid
        assert term[0]["event"] == "timeout"
        assert "deadline exceeded during failover" in term[0]["reason"]

    def test_all_replicas_dead_fails_cleanly(self, tiny_lm):
        router, sups, events = self._router(
            tiny_lm, n=2, router_kw=dict(retry_backoff_s=0.0,
                                         retry_jitter_s=0.0))
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 8)
                for i in range(2)]
        router.pump(1)
        router.kill_replica(0)
        router.kill_replica(1)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert sorted(term) == sorted(gids)
        assert all(e["event"] == "error" and "replica" in e["reason"]
                   for e in term.values())
        assert router.state is SupervisorState.FAILED
        assert router.exit_code == 1
        with pytest.raises(ShuttingDown):
            router.submit(np.arange(5, dtype=np.int32), 2)

    def test_cascade_drain_stops_everything(self, tiny_lm):
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=3)
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, sups[0].engine.assembly_len)
        gid = router.submit(p, 5)
        router.pump(1)
        router.request_drain("test over")
        assert router.draining
        with pytest.raises(ShuttingDown):
            router.submit(p, 2)
        router.run_sync()
        assert router.state is SupervisorState.STOPPED
        assert router.exit_code == 0
        assert router.drain_duration_s is not None
        assert all(s.state is SupervisorState.STOPPED for s in sups)
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref

    def test_stats_and_health_gauges_shape(self, tiny_lm):
        router, sups, _ = self._router(tiny_lm, n=2)
        router.submit(np.arange(5, dtype=np.int32), 4)
        st = router.stats()
        assert st["router_replicas"] == 2
        assert st["router_open_requests"] == 1
        assert len(st["replicas"]) == 2
        for r in st["replicas"]:
            assert r["breaker_state"] == "closed"
            assert not r["killed"]
        g = router.health_gauges()
        assert g["replicas_total"] == 2
        assert g["replicas_healthy"] == 2
        assert g["num_running"] == 1
        router.run_sync()
        assert router.stats()["router_open_requests"] == 0

    def test_threaded_router_submit_and_drain(self, tiny_lm):
        """The started (threaded) path: every replica on its own worker,
        the monitor probing health, drain from the outside."""
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=2)
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, sups[0].engine.assembly_len)
        router.start()
        import queue as _q
        got: "_q.Queue[dict]" = _q.Queue()
        gid = router.submit(p, 5, listener=got.put)
        ev = got.get(timeout=60)
        seen = [ev]
        while ev["event"] == "token":
            ev = got.get(timeout=60)
            seen.append(ev)
        assert ev["event"] == "done" and ev["tokens"] == ref
        assert [e["token"] for e in seen[:-1]] == ref
        router.request_drain("test over")
        assert router.join(timeout=30)
        assert router.state is SupervisorState.STOPPED
        assert router.exit_code == 0
        assert gid in {e["id"] for e in events}

    # -- gray-failure tolerance: health-scored placement -----------------------

    def test_uniform_scores_route_byte_identical_to_jsq(self, tiny_lm):
        """The degenerate case IS the old behaviour: with uniform health
        scores the weighted placement reduces to pure JSQ, down to the
        tie-breaks — replicas in index order, strictly-shorter wins."""
        router, sups, _ = self._router(tiny_lm, n=3)
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 4)
                for i in range(7)]
        placed = [router._open[g].replica for g in gids]
        assert placed == [0, 1, 2, 0, 1, 2, 0]
        assert [len(h.live) for h in router.replicas] == [3, 2, 2]
        router.run_sync()

    def test_dead_band_snaps_small_score_deltas_to_jsq(self, tiny_lm):
        """Scores inside the tolerance dead-band don't perturb placement:
        routing stays byte-identical to JSQ despite the noise."""
        router, sups, _ = self._router(tiny_lm, n=3)
        # score 1.x, ratio under 1 + score_tolerance (default 0.5)
        router.replicas[0].health.step_latency_s = 0.01
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 4)
                for i in range(7)]
        assert [router._open[g].replica for g in gids] == \
            [0, 1, 2, 0, 1, 2, 0]
        router.run_sync()

    def test_large_score_delta_steers_placement_away(self, tiny_lm):
        """A genuinely worse replica gets proportionally less work: its
        weighted queue key loses even at equal queue length."""
        router, sups, _ = self._router(tiny_lm, n=3)
        router.replicas[0].health.step_latency_s = 1.0   # score ~26
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 4)
                for i in range(6)]
        placed = [router._open[g].replica for g in gids]
        assert 0 not in placed
        assert [len(h.live) for h in router.replicas] == [0, 3, 3]
        router.run_sync()

    def test_score_tolerance_validated(self, tiny_lm):
        model, params = tiny_lm
        sup = EngineSupervisor(InferenceEngine(model, params, **self.KW))
        with pytest.raises(ValueError, match="score_tolerance"):
            Router([sup], score_tolerance=-0.1)

    def test_slow_replica_actuator(self, tiny_lm):
        """The replica.slow chaos actuator installs a per-step delay on a
        live engine (creating a FaultPlan when none exists) and delay<=0
        restores full speed."""
        router, sups, _ = self._router(tiny_lm, n=2)
        assert sups[0].engine.faults is None
        router.slow_replica(0, 0.02)
        assert sups[0].engine.faults.step_delay_s == 0.02
        assert sups[0].engine.faults.step_delay_calls == ()
        router.slow_replica(0, -1.0)
        assert sups[0].engine.faults.step_delay_s == 0.0

    # -- gray-failure tolerance: degraded-replica ejection ---------------------

    GRAY_KW = dict(hedge_budget=0.0, degrade_window_s=0.0,
                   degrade_cooldown_s=1000.0)

    def test_sustained_bad_score_ejects_replica(self, tiny_lm):
        """Score past degrade_factor × fleet median, sustained for the
        window, ejects the replica from placement: DEGRADED, not OPEN —
        its breaker is untouched because its calls still succeed."""
        router, sups, _ = self._router(
            tiny_lm, n=3, router_kw=dict(self.GRAY_KW))
        router.pump(1)
        router.replicas[0].health.step_latency_s = 1.0
        router._probe()                       # crossing: suspect_since set
        assert not router.replicas[0].degraded
        router._probe()                       # sustained: ejected
        assert router.replicas[0].degraded
        assert not router.replicas[0].available
        assert router.replicas[0].breaker.state is BreakerState.CLOSED
        assert router.metrics.degraded_ejections == 1
        # placement skips it entirely now
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 4)
                for i in range(4)]
        assert all(router._open[g].replica in (1, 2) for g in gids)
        router.run_sync()

    def test_ejection_proactively_migrates_live_streams(self, tiny_lm):
        """Ejecting a replica pulls its in-flight streams off BEFORE they
        fail: same token-exact recompute-resume as crash migration, old
        stream cancelled quietly, counted as proactive."""
        model, params = tiny_lm
        router, sups, events = self._router(
            tiny_lm, n=3, router_kw=dict(self.GRAY_KW, migration_budget=3))
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 8, sups[0].engine.assembly_len)
        gid = router.submit(p, 8)
        assert router._open[gid].replica == 0
        router.pump(2)                        # stream genuinely mid-flight
        router.replicas[0].health.step_latency_s = 1.0
        router._probe()
        router._probe()                       # ejects + migrates proactively
        assert router.metrics.degraded_ejections == 1
        assert router.metrics.proactive_migrations == 1
        assert router._open[gid].replica in (1, 2)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        streamed = [e["token"] for e in events if e["event"] == "token"]
        assert streamed == ref                # nothing duplicated or lost
        assert router.replicas[0].breaker.state is BreakerState.CLOSED

    def test_never_ejects_last_non_degraded_replica(self, tiny_lm):
        """The guard that keeps the fleet serving: however bad its score,
        the last non-degraded replica is never ejected."""
        router, sups, _ = self._router(
            tiny_lm, n=3, router_kw=dict(self.GRAY_KW))
        router.pump(1)
        router.replicas[1].degraded = True
        router.replicas[2].degraded = True
        router.replicas[0].health.step_latency_s = 5.0
        router._probe()
        router._probe()
        assert not router.replicas[0].degraded
        assert router.metrics.degraded_ejections == 0

    def test_recovered_replica_is_readmitted(self, tiny_lm):
        """Hysteresis readmission: once the score is back under
        readmit_factor × median for a sustained window, the replica
        rejoins placement."""
        router, sups, _ = self._router(
            tiny_lm, n=3, router_kw=dict(self.GRAY_KW))
        router.pump(1)
        router.replicas[0].health.step_latency_s = 1.0
        router._probe()
        router._probe()
        assert router.replicas[0].degraded
        router.replicas[0].health.step_latency_s = 0.0   # recovered
        router._probe()                       # back under: readmit timer
        router._probe()                       # sustained: readmitted
        assert not router.replicas[0].degraded
        assert router.replicas[0].available
        gids = [router.submit(np.arange(5, dtype=np.int32) + i, 4)
                for i in range(3)]
        assert sorted(router._open[g].replica for g in gids) == [0, 1, 2]
        router.run_sync()

    def test_recovery_probe_after_cooldown(self, tiny_lm):
        """Past the cooldown a degraded replica is offered ONE probe
        dispatch at a time so it can prove itself — no thundering herd
        back onto a replica that may still be sick."""
        router, sups, _ = self._router(
            tiny_lm, n=3, router_kw=dict(self.GRAY_KW))
        router.pump(1)
        router.replicas[0].health.step_latency_s = 1.0
        router._probe()
        router._probe()
        assert router.replicas[0].degraded
        g1 = router.submit(np.arange(5, dtype=np.int32), 6)
        g2 = router.submit(np.arange(6, dtype=np.int32), 6)
        assert {router._open[g].replica for g in (g1, g2)} == {1, 2}
        # cooldown elapses; the replica's score has recovered
        router.replicas[0].health.step_latency_s = 0.0
        router.degrade_cooldown_s = 0.0
        g3 = router.submit(np.arange(7, dtype=np.int32), 6)
        assert router._open[g3].replica == 0   # the probe dispatch
        assert router.replicas[0].recovery_probing
        g4 = router.submit(np.arange(8, dtype=np.int32), 6)
        assert router._open[g4].replica in (1, 2)   # one probe at a time
        router.run_sync()

    # -- gray-failure tolerance: hedged dispatch -------------------------------

    HEDGE_KW = dict(hedge_ttft_s=0.0, hedge_budget=1.0, degrade_factor=0.0)

    def test_overdue_request_hedges_and_dedupes(self, tiny_lm):
        """A first token past the threshold races a duplicate on another
        replica; the primary's first token wins, the duplicate is
        cancelled quietly, and the client stream carries every token
        exactly once."""
        model, params = tiny_lm
        router, sups, events = self._router(
            tiny_lm, n=2, router_kw=dict(self.HEDGE_KW))
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, sups[0].engine.assembly_len)
        gid = router.submit(p, 5)
        router._probe()                       # threshold 0: fires at once
        assert router.metrics.hedges_fired == 1
        rec = router._open[gid]
        assert rec.hedge_replica == 1 and rec.hedge_epoch is not None
        assert [len(h.live) for h in router.replicas] == [1, 1]
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert list(term) == [gid]            # exactly one terminal
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        streamed = [e["token"] for e in events if e["event"] == "token"]
        assert streamed == ref                # epoch guard deduped the race
        assert router.metrics.hedges_cancelled == 1
        # the loser never charges a breaker
        assert all(h.breaker.state is BreakerState.CLOSED
                   for h in router.replicas)
        for h in router.replicas:
            assert not h.live
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()

    def test_hedge_budget_bounds_amplification(self, tiny_lm):
        """The budget is consulted before EVERY fire: with every request
        overdue at once, only hedge_budget × open duplicates launch."""
        router, sups, _ = self._router(
            tiny_lm, n=3,
            router_kw=dict(self.HEDGE_KW, hedge_budget=0.4))
        for i in range(5):
            router.submit(np.arange(5, dtype=np.int32) + i, 4)
        router._probe()                       # all 5 overdue; cap = 2
        assert router.metrics.hedges_fired == 2
        router.run_sync()

    def test_hedge_disabled_when_budget_zero(self, tiny_lm):
        router, sups, _ = self._router(
            tiny_lm, n=2,
            router_kw=dict(self.HEDGE_KW, hedge_budget=0.0))
        router.submit(np.arange(5, dtype=np.int32), 4)
        router._probe()
        assert router.metrics.hedges_fired == 0
        router.run_sync()

    def test_hedge_fires_at_most_once_per_request(self, tiny_lm):
        router, sups, _ = self._router(
            tiny_lm, n=3, router_kw=dict(self.HEDGE_KW))
        router.submit(np.arange(5, dtype=np.int32), 4)
        router._probe()
        assert router.metrics.hedges_fired == 1
        router._probe()                       # still overdue, already hedged
        router._probe()
        assert router.metrics.hedges_fired == 1
        router.run_sync()

    def test_hedge_promoted_when_primary_dies(self, tiny_lm):
        """Primary replica hard-killed with a hedge in flight: the
        duplicate is promoted in place (hedges_won) — no fresh migration
        dispatch, and the stream finishes token-exact."""
        model, params = tiny_lm
        router, sups, events = self._router(
            tiny_lm, n=2, router_kw=dict(self.HEDGE_KW))
        p = np.arange(6, dtype=np.int32)
        ref = _greedy_ref(model, params, p, 5, sups[0].engine.assembly_len)
        gid = router.submit(p, 5)
        assert router._open[gid].replica == 0
        router._probe()                       # hedge racing on replica 1
        assert router.metrics.hedges_fired == 1
        router.kill_replica(0)
        rec = router._open[gid]
        assert rec.replica == 1               # duplicate promoted to primary
        assert router.metrics.hedges_won == 1
        assert router.metrics.migrated_requests == 0
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        streamed = [e["token"] for e in events if e["event"] == "token"]
        assert streamed == ref

    def test_adaptive_threshold_needs_ttft_samples(self, tiny_lm):
        """hedge_ttft_s=None means adaptive: no hedging until the rolling
        TTFT window holds enough samples to trust a p95."""
        router, sups, _ = self._router(
            tiny_lm, n=2,
            router_kw=dict(hedge_ttft_s=None, hedge_budget=1.0,
                           degrade_factor=0.0))
        assert router._hedge_threshold_locked() is None
        router.submit(np.arange(5, dtype=np.int32), 4)
        router._probe()                       # no threshold yet: no hedge
        assert router.metrics.hedges_fired == 0
        router._ttft_window.extend([0.01] * 8)
        thr = router._hedge_threshold_locked()
        assert thr == pytest.approx(0.01)
        router.run_sync()

    def test_fixed_threshold_wins_over_adaptive(self, tiny_lm):
        router, sups, _ = self._router(
            tiny_lm, n=2, router_kw=dict(hedge_ttft_s=0.123))
        router._ttft_window.extend([0.01] * 64)
        assert router._hedge_threshold_locked() == pytest.approx(0.123)

    # -- gray-failure tolerance: observability ---------------------------------

    def test_gray_failure_stats_and_gauges_shape(self, tiny_lm):
        router, sups, _ = self._router(tiny_lm, n=2)
        router.submit(np.arange(5, dtype=np.int32), 4)
        st = router.stats()
        for k in ("hedges_fired", "hedges_won", "hedges_cancelled",
                  "degraded_ejections", "proactive_migrations"):
            assert st[k] == 0
        for r in st["replicas"]:
            assert r["degraded"] is False
            assert r["health_score"] >= 1.0
        g = router.health_gauges()
        assert g["replicas_degraded"] == 0
        for k in ("hedges_fired", "hedges_won", "hedges_cancelled",
                  "degraded_ejections", "proactive_migrations"):
            assert g[k] == 0
        router.run_sync()

    def test_health_score_prometheus_family(self, tiny_lm):
        """The per-replica health-score gauge survives the router-label
        merge: one sample per replica, each keeping its own index."""
        router, sups, _ = self._router(tiny_lm, n=3)
        fams = {f["name"]: f for f in router.prometheus_series()}
        fam = fams["tnn_serve_replica_health_score"]
        assert fam["type"] == "gauge"
        labels = sorted(lbls["replica"] for _, lbls, _ in fam["samples"])
        assert labels == ["0", "1", "2"]
        assert all(v >= 1.0 for _, _, v in fam["samples"])
        for name in ("tnn_serve_hedges_fired_total",
                     "tnn_serve_hedges_won_total",
                     "tnn_serve_hedges_cancelled_total",
                     "tnn_serve_degraded_ejections_total",
                     "tnn_serve_proactive_migrations_total"):
            assert name in fams, name

    def test_gray_failure_metrics_counters(self):
        from tnn_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.observe_hedge_fired()
        m.observe_hedge_won()
        m.observe_hedge_cancelled()
        m.observe_ejection()
        m.observe_proactive_migration()
        m.observe_proactive_migration()
        s = m.summary()
        assert s["hedges_fired"] == 1
        assert s["hedges_won"] == 1
        assert s["hedges_cancelled"] == 1
        assert s["degraded_ejections"] == 1
        assert s["proactive_migrations"] == 2


@pytest.mark.slow
def test_gray_failure_chaos_soak(tiny_lm):
    """The gray-failure gate: 3 replicas with the full gray fault surface
    composed — one replica turned persistently slow on a seeded schedule
    (replica.slow), flaky per-replica call drops (net.flaky), a seeded
    router↔replica partition window (net.partition), and a mid-run hard
    kill — with hedging and degraded-ejection live. Asserts the whole
    contract: exactly one terminal per admitted request, hedged streams'
    tokens delivered exactly once, every finished stream token-exact
    against the fault-free reference, zero leaked blocks on survivors."""
    model, params = tiny_lm
    rng = np.random.default_rng(33)
    uniq = [rng.integers(0, 128, int(n)).astype(np.int32)
            for n in rng.integers(4, 12, 6)]
    max_new = 5
    sups = [EngineSupervisor(
                InferenceEngine(model, params, num_blocks=32, block_size=4,
                                max_batch_size=4, max_seq_len=32,
                                max_queue_depth=24),
                restart_backoff_s=0.0)
            for _ in range(3)]
    refs = {i: _greedy_ref(model, params, p, max_new,
                           sups[0].engine.assembly_len)
            for i, p in enumerate(uniq)}
    events = []
    net = FaultPlan(seed=41, flaky_replica=1, flaky_drop_prob=0.15,
                    net_partition_calls=(12,), net_partition_rounds=2)
    router = Router(sups, event_sink=events.append, seed=4, faults=net,
                    retry_backoff_s=0.0, retry_jitter_s=0.0,
                    hedge_ttft_s=0.05, hedge_budget=0.3,
                    degrade_factor=2.0, degrade_window_s=0.05,
                    degrade_cooldown_s=60.0)
    chaos = FaultPlan(seed=9, replica_slow_calls=(8,),
                      replica_kill_calls=(22,))
    n_requests, rejected, submitted = 40, 0, {}
    slow_idx, victim = None, None
    for i in range(n_requests):
        which = int(rng.integers(0, len(uniq)))
        try:
            gid = router.submit(uniq[which], max_new)
            submitted[gid] = which
        except (AdmissionRejected, ShuttingDown, ConnectionError):
            rejected += 1
        router.pump(1)
        if slow_idx is None and chaos.replica_slow():
            # the plan decides WHEN; the harness picks WHICH: the busiest
            slow_idx = max((h for h in router.replicas if not h.killed),
                           key=lambda h: len(h.live)).idx
            router.slow_replica(slow_idx, 0.02)
        if victim is None and chaos.replica_kill():
            victim = max((h for h in router.replicas
                          if not h.killed and h.idx != slow_idx),
                         key=lambda h: len(h.live)).idx
            router.kill_replica(victim)
    router.run_sync()
    router.request_drain("gray soak complete")
    router.run_sync()

    # every composed fault actually fired
    assert chaos.fired["replica.slow"] == 1 and slow_idx is not None
    assert chaos.fired["replica.kill"] == 1 and victim is not None
    assert net.fired["net.partition"] == 1
    assert net.fired["net.flaky"] >= 1
    assert router.state is SupervisorState.STOPPED
    assert router.exit_code == 0
    assert rejected + len(submitted) == n_requests
    # exactly one terminal event per admitted request
    terminals = [e for e in events if e["event"] != "token"]
    per_gid = {}
    for e in terminals:
        per_gid[e["id"]] = per_gid.get(e["id"], 0) + 1
    assert sorted(per_gid) == sorted(submitted)
    assert all(c == 1 for c in per_gid.values()), per_gid
    # finished streams token-exact, hedged tokens delivered exactly once
    finished = [e for e in terminals if e["event"] == "done"]
    assert finished, "gray soak finished nothing"
    for e in finished:
        assert e["tokens"] == refs[submitted[e["id"]]], \
            f"gid {e['id']} diverged from fault-free reference"
        streamed = [t["token"] for t in events
                    if t["event"] == "token" and t["id"] == e["id"]]
        assert streamed == e["tokens"], \
            f"gid {e['id']}: hedged stream duplicated or dropped tokens"
    # zero leaked blocks on the survivors
    for h in router.replicas:
        if h.idx != victim:
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()


@pytest.mark.slow
def test_chaos_soak_router(tiny_lm):
    """The replicated soak gate: 3 replicas behind the router with chaos
    at every layer — alloc faults and NaN rows inside each replica, one
    replica hard-killed mid-run on a seeded schedule. Asserts the full
    failover contract: exactly one terminal event per request, finished
    streams (migrants included) token-exact against the fault-free
    reference, zero leaked blocks on the survivors, clean cascade drain."""
    model, params = tiny_lm
    rng = np.random.default_rng(21)
    uniq = [rng.integers(0, 128, int(n)).astype(np.int32)
            for n in rng.integers(4, 14, 8)]
    max_new = 6
    sups = []
    for i in range(3):
        plan = FaultPlan(seed=100 + i, alloc_fail_prob=0.02,
                         nan_logit_prob=0.01)
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              max_queue_depth=24, faults=plan)
        eng.pool.fault_plan = plan
        sups.append(EngineSupervisor(eng, restart_backoff_s=0.0,
                                     max_restarts=5))
    refs = {i: _greedy_ref(model, params, p, max_new,
                           sups[0].engine.assembly_len)
            for i, p in enumerate(uniq)}
    events = []
    router = Router(sups, event_sink=events.append, seed=3)
    kill_plan = FaultPlan(seed=9, replica_kill_calls=(40,))
    n_requests, rejected, submitted = 120, 0, {}
    victim = None
    for i in range(n_requests):
        which = int(rng.integers(0, len(uniq)))
        try:
            gid = router.submit(uniq[which], max_new, priority=i % 3)
            submitted[gid] = which
        except (AdmissionRejected, ShuttingDown, ConnectionError):
            rejected += 1
        router.pump(1)
        if victim is None and kill_plan.replica_kill():
            victim = max((h for h in router.replicas if not h.killed),
                         key=lambda h: len(h.live)).idx
            router.kill_replica(victim)
    router.run_sync()
    router.request_drain("soak complete")
    router.run_sync()

    assert victim is not None, "the seeded kill never fired"
    assert kill_plan.fired["replica.kill"] == 1
    assert router.state is SupervisorState.STOPPED
    assert router.exit_code == 0
    assert rejected + len(submitted) == n_requests
    # exactly one terminal event per admitted request
    terminals = [e for e in events if e["event"] != "token"]
    per_gid = {}
    for e in terminals:
        per_gid[e["id"]] = per_gid.get(e["id"], 0) + 1
    assert sorted(per_gid) == sorted(submitted)
    assert all(c == 1 for c in per_gid.values()), per_gid
    # the kill migrated live work, and the migrants landed
    assert router.metrics.migrated_requests > 0
    finished = [e for e in terminals if e["event"] == "done"]
    assert finished, "soak finished nothing"
    for e in finished:
        assert e["tokens"] == refs[submitted[e["id"]]], \
            f"gid {e['id']} diverged from fault-free reference"
    # zero leaked blocks on the survivors
    for h in router.replicas:
        if h.idx != victim:
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()


# -- speculative decoding: drafters, rollback, token-exact verification -------


def _cyclic_prompts(n, seed=0, vocab=128):
    """Short-period cyclic token streams. The n-gram drafter finds its own
    suffix immediately, and a greedy model on repetitive context tends to
    keep the loop going — so drafts are reliably proposed AND accepted
    without depending on trained weights."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        motif = rng.integers(0, vocab, int(rng.integers(2, 5)))
        out.append(np.tile(motif, int(rng.integers(3, 6))).astype(np.int32))
    return out


@pytest.fixture(scope="module")
def draft_lm(tiny_lm):
    """The zoo's draft-model config, sharing the target's vocab/max_len."""
    from tnn_tpu.models.zoo import create

    model, _ = tiny_lm
    draft = create("gpt2_tiny", vocab_size=model.vocab_size,
                   max_len=model.max_len)
    params = draft.init(jax.random.PRNGKey(1), (1, 8))["params"]
    return draft, params


class TestDrafters:
    """Host-side drafter unit tests — no engine, no pool."""

    def _req(self, prompt, out=()):
        import types

        return types.SimpleNamespace(
            prompt=np.asarray(prompt, np.int32), out_tokens=list(out))

    def test_ngram_copies_continuation_of_repeated_suffix(self):
        from tnn_tpu.serving.spec_decode import NGramDrafter

        d = NGramDrafter(max_n=3)
        req = self._req([1, 2, 3, 1, 2, 3, 1, 2])
        assert d.draft(req, 3) == [3, 1, 2]
        assert d.draft(req, 1) == [3]

    def test_ngram_silent_on_novel_context(self):
        from tnn_tpu.serving.spec_decode import NGramDrafter

        assert NGramDrafter().draft(self._req(np.arange(8)), 4) == []

    def test_ngram_sees_generated_tokens(self):
        """The lookup context is prompt + out_tokens (including the pending
        next_token), so output-side loops draft themselves too."""
        from tnn_tpu.serving.spec_decode import NGramDrafter

        req = self._req([7, 8], out=[9, 7, 8])
        assert NGramDrafter().draft(req, 2) == [9, 7]

    def test_ngram_validates_orders(self):
        from tnn_tpu.serving.spec_decode import NGramDrafter

        with pytest.raises(ValueError, match="min_n"):
            NGramDrafter(max_n=2, min_n=3)

    def test_draft_model_deterministic_and_in_vocab(self, draft_lm):
        from tnn_tpu.serving.spec_decode import DraftModelDrafter

        model, params = draft_lm
        d = DraftModelDrafter(model, params)
        req = self._req(np.arange(8) % 128)
        a, b = d.draft(req, 4), d.draft(req, 4)
        assert a == b and len(a) == 4
        assert all(0 <= t < model.vocab_size for t in a)

    def test_draft_model_clamps_at_position_cap(self, draft_lm):
        """Near the draft model's own max_len the proposal shrinks; at the
        cap it vanishes — never an out-of-range position."""
        from tnn_tpu.serving.spec_decode import DraftModelDrafter

        model, params = draft_lm
        d = DraftModelDrafter(model, params)
        assert d.draft(
            self._req(np.zeros(model.max_len, np.int32)), 4) == []
        near = d.draft(self._req(np.zeros(model.max_len - 2, np.int32)), 4)
        assert len(near) == 2


class TestSchedulerSpecBudget:
    def _sched(self, spec_tokens):
        sched = Scheduler(max_batch_size=4, token_budget=10, chunk_size=8,
                          spec_tokens=spec_tokens)
        dec = _req(0, 4, max_new=8)
        dec.prefill_len = 4
        dec.cache_len = 4                 # decode phase
        pre = _req(1, 12, max_new=8)
        pre.prefill_len = 12
        pre.cache_len = 4                 # mid-prefill: 8 prompt tokens left
        sched.admit(dec)
        sched.admit(pre)
        return sched

    def test_decode_rows_reserve_draft_budget(self):
        pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=2,
                           num_blocks=9, block_size=4)
        assert self._sched(0).schedule(pool).chunks == {1: 8}
        # each decode row now costs 1 + spec_tokens of the step budget:
        # 10 - 5 leaves a 5-token chunk grant instead of 8
        assert self._sched(4).schedule(pool).chunks == {1: 5}

    def test_negative_spec_tokens_rejected(self):
        with pytest.raises(ValueError, match="spec_tokens"):
            Scheduler(max_batch_size=4, token_budget=10, spec_tokens=-1)


class TestPoolTruncate:
    """truncate() is the speculative-rollback primitive; check_invariants
    grew per-row seq_len checks to catch both ways it can go wrong."""

    def _pool(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("head_dim", 2)
        kw.setdefault("num_blocks", 8)
        kw.setdefault("block_size", 4)
        return PagedKVPool(**kw)

    def test_truncate_frees_rejected_tail(self):
        pool = self._pool()
        table = pool.alloc(4)              # headroom for 16 positions
        kept = pool.truncate(table, 9)     # verifier kept 9 resident tokens
        assert kept == table[:3]
        assert pool.num_allocated == 3
        pool.check_invariants([kept], [9])

    def test_truncate_noop_when_table_tight(self):
        pool = self._pool()
        table = pool.alloc(2)
        assert pool.truncate(table, 8) == table
        assert pool.num_allocated == 2

    def test_truncate_to_zero_frees_everything(self):
        pool = self._pool()
        table = pool.alloc(3)
        assert pool.truncate(table, 0) == []
        assert pool.num_allocated == 0 and pool.num_free == pool.capacity

    def test_truncate_parks_indexed_blocks_evictable(self):
        """Rollback preserves the free/allocated/evictable partition: freed
        tail blocks the prefix cache still indexes park in the LRU instead
        of returning to the free list."""
        pool = self._pool()
        table = pool.alloc(4)
        cached = set(table[2:])
        pool.evictable_filter = cached.__contains__
        kept = pool.truncate(table, 5)
        assert kept == table[:2]
        assert pool.num_evictable == 2 and pool.num_allocated == 2
        assert pool.num_free + pool.num_evictable + pool.num_allocated \
            == pool.capacity
        pool.check_invariants([kept], [5])

    def test_truncated_too_deep_detected(self):
        pool = self._pool()
        table = pool.alloc(1)              # covers 4 positions only
        with pytest.raises(ValueError, match="truncated too deep"):
            pool.check_invariants([table], [9])

    def test_stale_draft_tail_detected(self):
        """A row that grew blocks for 1+k candidates but skipped rollback
        after rejection holds more than blocks_for(n + 1) blocks."""
        pool = self._pool()
        table = pool.alloc(4)
        with pytest.raises(ValueError, match="stale tail"):
            pool.check_invariants([table], [4])   # 4 resident: max 2 blocks
        pool.check_invariants([pool.truncate(table, 4)], [4])

    def test_seq_lens_must_parallel_tables(self):
        pool = self._pool()
        table = pool.alloc(1)
        with pytest.raises(ValueError, match="not parallel"):
            pool.check_invariants([table], [4, 4])


class TestSpecDecode:
    """The PR 7 tentpole: drafted tokens ride the EXISTING mixed step as
    ragged q_lens = k+1 rows; greedy verification must be token-exact
    against the offline reference under every schedule, and rollback must
    leave pool bookkeeping clean."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _eng(self, lm, draft_lm=None, spec="ngram", **kw):
        model, params = lm
        merged = dict(self.KW)
        merged.update(kw)
        if spec == "draft":
            dm, dp = draft_lm
            merged.update(draft_model=dm, draft_params=dp)
        return InferenceEngine(model, params, spec=spec, **merged)

    def _staggered(self, eng, prompts, max_new=10):
        rids = [eng.submit(prompts[0], max_new)]
        eng.step(); eng.step()
        rids += [eng.submit(p, max_new) for p in prompts[1:]]
        out = eng.run_until_complete()
        return [out[r] for r in rids]

    @pytest.mark.parametrize("family", FAMILIES)
    def test_ngram_staggered_parity(self, lm, family):
        model, params = lm
        prompts = _cyclic_prompts(4, seed=0)
        eng = self._eng(lm)
        outs = self._staggered(eng, prompts)
        for toks, p in zip(outs, prompts):
            assert toks == _greedy_ref(model, params, p, 10,
                                       eng.assembly_len)
        s = eng.metrics.summary()
        assert s["spec_draft_tokens"] > 0, "drafter never fired — dead test"
        assert s["spec_acceptance_rate"] > 0
        # spec rows compile under their own key; widths stay pow2-bucketed
        spec_keys = [k for k in eng._jit
                     if k[0] == "mixed" and k[-1] == "spec"]
        assert spec_keys, "no spec mixed program was ever compiled"
        assert all(k[2] & (k[2] - 1) == 0 for k in spec_keys)
        _assert_drained(eng)

    # both variants re-pay the draft-model jit cache; the draft axis keeps
    # a tier-1 gate via the spec_draft crash-resume matrix entry
    @pytest.mark.slow
    @pytest.mark.parametrize("family", FAMILIES)
    def test_draft_model_staggered_parity(self, lm, draft_lm, family):
        model, params = lm
        prompts = _cyclic_prompts(4, seed=1)
        eng = self._eng(lm, draft_lm, spec="draft")
        outs = self._staggered(eng, prompts)
        for toks, p in zip(outs, prompts):
            assert toks == _greedy_ref(model, params, p, 10,
                                       eng.assembly_len)
        assert eng.metrics.summary()["spec_draft_tokens"] > 0
        _assert_drained(eng)

    def test_spec_off_engine_is_untouched(self, tiny_lm):
        """spec="off" must not even build spec programs: every mixed compile
        key keeps its 4-tuple shape, and the gauges say so."""
        eng = self._eng(tiny_lm, spec="off")
        self._staggered(eng, _cyclic_prompts(4, seed=0))
        assert all(len(k) == 4 for k in eng._jit if k[0] == "mixed")
        s = eng.stats()
        assert s["spec"] == "off" and s["spec_k"] == 0
        assert eng.metrics.summary()["mean_accepted_per_step"] == 0.0

    def test_preemption_parity_with_rollback(self, tiny_lm):
        """A starved pool preempts speculating rows mid-stream; rollback +
        recompute-requeue must stay byte-identical to the offline reference
        and drain with zero leaks."""
        model, params = tiny_lm
        prompts = _cyclic_prompts(4, seed=2)
        eng = self._eng(tiny_lm, num_blocks=9)
        for p in prompts:
            eng.submit(p, 10)
        out = eng.run_until_complete()
        assert eng.metrics.preemptions > 0, "pool was never exhausted"
        for rid, p in enumerate(prompts):
            assert out[rid] == _greedy_ref(model, params, p, 10,
                                           eng.assembly_len)
        _assert_drained(eng)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_prefix_cache_hits_stay_exact(self, lm, family):
        """Shared-prefix admission (forked tables, COW) composes with
        speculation: cached rows still verify token-exact."""
        model, params = lm
        rng = np.random.default_rng(3)
        prefix = np.tile(rng.integers(0, 128, 3), 4).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(0, 128, 4)
                                   .astype(np.int32)]) for _ in range(4)]
        eng = self._eng(lm)
        rids = []
        for p in prompts:
            rids.append(eng.submit(p, 8))
            eng.step()
        out = eng.run_until_complete()
        assert eng.metrics.prefill_tokens_saved > 0, "cache never hit"
        for rid, p in zip(rids, prompts):
            assert out[rid] == _greedy_ref(model, params, p, 8,
                                           eng.assembly_len)
        _assert_drained(eng)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_stop_token_mid_draft_clips_commit(self, lm, family):
        """A stop token inside an accepted draft run clips the commit at the
        stop position — trailing accepted tokens are discarded, exactly as
        sequential decode would never have produced them."""
        model, params = lm
        p = _cyclic_prompts(1, seed=4)[0]
        eng = self._eng(lm)
        ref = _greedy_ref(model, params, p, 10, eng.assembly_len)
        stop = ref[3]
        rid = eng.submit(p, 10, stop_token=stop)
        out = eng.run_until_complete()
        # cyclic streams repeat tokens: the FIRST occurrence wins, exactly
        # as sequential decode would have stopped
        assert out[rid] == ref[:ref.index(stop) + 1]
        assert eng.result(rid).finish_reason == "stop_token"
        _assert_drained(eng)

    def test_max_new_clamp_never_overshoots(self, tiny_lm):
        """k is clamped to the remaining generation budget, so accepted
        drafts can never commit past max_new_tokens."""
        model, params = tiny_lm
        p = _cyclic_prompts(1, seed=5)[0]
        eng = self._eng(tiny_lm, spec_k=6)
        ref = _greedy_ref(model, params, p, 5, eng.assembly_len)
        rid = eng.submit(p, 5)
        out = eng.run_until_complete()
        assert out[rid] == ref
        assert eng.result(rid).finish_reason == "length"
        _assert_drained(eng)

    def test_stochastic_spec_stays_in_vocab(self, tiny_lm):
        """The rejection-sampling path: stochastic rows speculate too, and
        co-batched greedy rows stay exact. (Cross-schedule distributional
        equality is the verifier's rejection-sampling construction; draw
        sequences legitimately differ from the spec-off stream.)"""
        model, params = tiny_lm
        eng = self._eng(tiny_lm, seed=3)
        p = _cyclic_prompts(1, seed=6)[0]
        g = eng.submit(p, 8)
        s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
        out = eng.run_until_complete()
        assert out[g] == _greedy_ref(model, params, p, 8, eng.assembly_len)
        assert len(out[s]) == 8
        assert all(0 <= t < model.vocab_size for t in out[s])
        _assert_drained(eng)

    def test_spec_metrics_and_stats(self, tiny_lm):
        eng = self._eng(tiny_lm, spec_k=4)
        for p in _cyclic_prompts(4, seed=0):
            eng.submit(p, 12)
        eng.run_until_complete()
        s = eng.metrics.summary()
        assert s["spec_draft_tokens"] >= s["spec_accepted_tokens"] > 0
        assert 0 < s["spec_acceptance_rate"] <= 1
        assert s["mean_accepted_per_step"] > 1, \
            "speculation never beat sequential decode on cyclic prompts"
        assert "token_latency_ms_p99" in s
        st = eng.stats()
        assert st["spec"] == "ngram" and st["spec_k"] == 4
        assert st["compiled_step_signatures"] == len(eng._jit) >= 1

    def test_custom_drafter_instance_accepted(self, tiny_lm):
        from tnn_tpu.serving.spec_decode import NGramDrafter

        eng = self._eng(tiny_lm, spec=NGramDrafter(max_n=2))
        assert eng.stats()["spec"] == "ngram"
        p = _cyclic_prompts(1, seed=7)[0]
        model, params = tiny_lm
        rid = eng.submit(p, 8)
        out = eng.run_until_complete()
        assert out[rid] == _greedy_ref(model, params, p, 8,
                                       eng.assembly_len)

    def test_constructor_validation(self, tiny_lm, draft_lm):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="unknown spec"):
            InferenceEngine(model, params, spec="turbo", **self.KW)
        with pytest.raises(ValueError, match="draft_model"):
            InferenceEngine(model, params, spec="draft", **self.KW)
        with pytest.raises(ValueError, match="spec_k"):
            InferenceEngine(model, params, spec="ngram", spec_k=0,
                            **self.KW)
        # the one serving path needs the model's paged method: a model
        # without it is refused at start-up, in one sentence
        plain = type("NoPaged", (), {"kv_cache_dtype": None})()
        with pytest.raises(ValueError, match="NoPaged has no apply_paged"):
            InferenceEngine(plain, params, **self.KW)
        from tnn_tpu.models.gpt2 import gpt2_tiny

        wrong = gpt2_tiny(vocab_size=64, max_len=64)
        wp = wrong.init(jax.random.PRNGKey(2), (1, 8))["params"]
        with pytest.raises(ValueError, match="vocab"):
            InferenceEngine(model, params, spec="draft", draft_model=wrong,
                            draft_params=wp, **self.KW)


class TestSpecChaos:
    """Chaos gate over speculation: alloc faults + NaN rows + poisoned
    drafts. Every request terminal, survivors byte-identical to a
    fault-free spec-OFF run (speculation plus faults may never change a
    committed token), zero leaked blocks."""

    KW = dict(num_blocks=16, block_size=4, max_batch_size=4, max_seq_len=32)

    @pytest.mark.parametrize(
        "spec", ["ngram", pytest.param("draft", marks=pytest.mark.slow)])
    def test_chaos_gate_spec(self, tiny_lm, draft_lm, spec):
        model, params = tiny_lm
        prompts = _cyclic_prompts(8, seed=7)
        kw = dict(self.KW)
        if spec == "draft":
            kw.update(draft_model=draft_lm[0], draft_params=draft_lm[1])
        ref_eng = InferenceEngine(model, params, **self.KW)
        ref_rids = [ref_eng.submit(p, 8) for p in prompts]
        ref_eng.run_until_complete()
        plan = FaultPlan(seed=9, alloc_fail_prob=0.12, nan_logit_calls=(3,),
                         draft_poison_prob=0.3)
        eng = InferenceEngine(model, params, spec=spec, faults=plan, **kw)
        rids = [eng.submit(p, 8) for p in prompts]
        eng.run_until_complete()
        assert plan.fired["pool.alloc"] >= 1, "alloc chaos never fired"
        assert plan.fired["draft.poison"] >= 1, "draft chaos never fired"
        states = [eng.result(r).state for r in rids]
        assert all(st in TERMINAL_STATES for st in states)
        assert RequestState.FINISHED in states, "no request survived"
        out, ref = _finished(eng), _finished(ref_eng)
        for rid, ref_rid in zip(rids, ref_rids):
            if rid in out:
                assert out[rid] == ref[ref_rid], f"survivor {rid} diverged"
        _assert_drained(eng)

    def test_poisoned_drafts_cost_acceptance_only(self, tiny_lm):
        """Poison EVERY draft: output still exact, acceptance reflects that
        corrupted proposals were rejected wholesale."""
        model, params = tiny_lm
        p = _cyclic_prompts(1, seed=8)[0]
        plan = FaultPlan(draft_poison_prob=1.0)
        eng = InferenceEngine(model, params, spec="ngram", faults=plan,
                              **self.KW)
        rid = eng.submit(p, 10)
        out = eng.run_until_complete()
        assert out[rid] == _greedy_ref(model, params, p, 10,
                                       eng.assembly_len)
        assert plan.fired["draft.poison"] > 0
        s = eng.metrics.summary()
        assert s["spec_draft_tokens"] > 0
        _assert_drained(eng)


@pytest.mark.slow
def test_gpt2_small_spec_ngram_staggered():
    """Acceptance bar for speculation at model scale: 8 staggered cyclic
    prompts on gpt2_small with spec="ngram", surviving preemption.

    Correctness is asserted by TEACHER FORCING, like
    test_gpt2_small_staggered_greedy: the spec verifier runs a differently
    fused program than sequential decode, so whole-sequence equality against
    a spec-off engine is ill-posed at this depth (top-2 logit gaps sit below
    f32 reduction noise). Every committed token must be the reference argmax
    up to fp near-ties, and speculation must actually accept drafts."""
    from tnn_tpu.models.zoo import create

    model = create("gpt2_small")
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = [np.tile(rng.integers(0, model.vocab_size, 3), 4)
               .astype(np.int32) for _ in range(8)]
    max_new = 16
    eng = InferenceEngine(model, params, num_blocks=14, block_size=16,
                          max_batch_size=8, max_seq_len=32, spec="ngram")
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if i % 3 == 2:
            eng.step()
    out = eng.run_until_complete()
    assert all(len(out[rid]) == max_new for rid in rids)
    assert eng.metrics.summary()["spec_accepted_tokens"] > 0, \
        "speculation never accepted a draft on cyclic prompts"

    seqs = np.stack([np.concatenate([prompts[i], out[rids[i]]])
                     for i in range(len(rids))])
    caches = model.init_cache(len(rids), seqs.shape[1])
    logits, _ = model.apply_cached(params, jnp.asarray(seqs), caches, 0)
    logits = np.asarray(logits, np.float64)
    plen = len(prompts[0])
    exact, ties = 0, []
    for i in range(len(rids)):
        for j in range(max_new):
            row = logits[i, plen + j - 1]
            chosen = seqs[i, plen + j]
            if chosen == row.argmax():
                exact += 1
            else:
                ties.append(float(row.max() - row[chosen]))
    total = len(rids) * max_new
    assert exact >= 0.9 * total, f"only {exact}/{total} tokens were argmax"
    assert all(m < 0.05 for m in ties), f"non-tie divergence: {ties}"
    _assert_drained(eng)


# -- host-RAM KV tier + elastic fleet (PR: elastic fleet resilience) ----------


class TestFaultPlanFleetSites:
    """Seed-determinism for the tier/scaling chaos sites, in the same
    shape as the client/replica site tests above: identical seeds replay
    identical fire schedules, scheduled calls fire at exact positions."""

    def test_tier_sites_are_deterministic(self):
        def trace(plan):
            return [(plan.tier_demote_fail(), plan.tier_corrupt(),
                     plan.tier_slow_readmit()) for _ in range(48)]

        kw = dict(tier_demote_fail_prob=0.3, tier_corrupt_prob=0.25,
                  tier_slow_readmit_prob=0.2)
        a = trace(FaultPlan(seed=5, **kw))
        b = trace(FaultPlan(seed=5, **kw))
        c = trace(FaultPlan(seed=6, **kw))
        assert a == b
        assert a != c
        assert any(t[0] for t in a) and any(t[1] for t in a) \
            and any(t[2] for t in a)
        plan = FaultPlan(seed=5, **kw)
        trace(plan)
        assert plan.calls["tier.demote_fail"] == 48
        assert plan.fired["tier.demote_fail"] == sum(t[0] for t in a)
        assert plan.fired["tier.corrupt"] == sum(t[1] for t in a)
        assert plan.fired["tier.slow_readmit"] == sum(t[2] for t in a)

    def test_scheduled_tier_calls_fire_exactly(self):
        plan = FaultPlan(tier_demote_fail_calls=(2,),
                         tier_corrupt_calls=(1, 3),
                         tier_slow_readmit_calls=(2,))
        assert [plan.tier_demote_fail() for _ in range(3)] == \
            [False, True, False]
        assert [plan.tier_corrupt() for _ in range(3)] == \
            [True, False, True]
        assert [plan.tier_slow_readmit() for _ in range(3)] == \
            [False, True, False]
        assert plan.fired["tier.demote_fail"] == 1
        assert plan.fired["tier.corrupt"] == 2
        assert plan.fired["tier.slow_readmit"] == 1

    def test_scale_join_site_is_deterministic(self):
        def trace(plan):
            return [plan.scale_join_fail() for _ in range(48)]

        a = trace(FaultPlan(seed=5, scale_join_fail_prob=0.3))
        b = trace(FaultPlan(seed=5, scale_join_fail_prob=0.3))
        c = trace(FaultPlan(seed=6, scale_join_fail_prob=0.3))
        assert a == b
        assert a != c
        assert any(a) and not all(a)
        plan = FaultPlan(seed=5, scale_join_fail_prob=0.3)
        trace(plan)
        assert plan.calls["scale.join_fail"] == 48
        assert plan.fired["scale.join_fail"] == sum(a)

    def test_scheduled_scale_join_calls_fire_exactly(self):
        plan = FaultPlan(scale_join_fail_calls=(1, 3))
        assert [plan.scale_join_fail() for _ in range(4)] == \
            [True, False, True, False]
        assert plan.fired["scale.join_fail"] == 2


class TestHostKVTier:
    """Tier unit tests — no engine, no pool: demote/verify roundtrip,
    digest enforcement, LRU bounds, fault sites, byte accounting."""

    def _leaves(self, seed=0, shape=(2, 4, 2), dtype=np.float32):
        rng = np.random.default_rng(seed)
        k = rng.standard_normal(shape).astype(dtype)
        v = rng.standard_normal(shape).astype(dtype)
        return (k, v)

    def test_demote_verify_roundtrip(self):
        tier = HostKVTier(1 << 20)
        leaves = self._leaves(1)
        assert tier.demote(b"key-a", leaves)
        assert b"key-a" in tier and len(tier) == 1
        assert tier.bytes_used == sum(x.nbytes for x in leaves)
        out = tier.verify_readmit(b"key-a")
        assert out is not None
        np.testing.assert_array_equal(out[0], leaves[0])
        np.testing.assert_array_equal(out[1], leaves[1])
        # a successful readmit REMOVES the entry (it is device-resident
        # again and will re-demote on its next eviction)
        assert b"key-a" not in tier and tier.bytes_used == 0
        s = tier.stats()
        assert s["tier_demotions"] == 1 and s["tier_readmits"] == 1
        assert s["tier_corrupt_dropped"] == 0
        tier.check_invariants()

    def test_miss_returns_none(self):
        tier = HostKVTier(1 << 20)
        assert tier.verify_readmit(b"never-demoted") is None
        assert tier.stats()["tier_corrupt_dropped"] == 0

    def test_real_corruption_is_dropped_not_served(self):
        """Bit rot planted straight into the stored leaf (no fault plan):
        the digest recomputation catches it, the entry is dropped, the
        caller sees an uncached miss — never wrong KV."""
        tier = HostKVTier(1 << 20)
        tier.demote(b"key-a", self._leaves(2))
        entry = tier._entries[b"key-a"]
        entry.leaves[0].reshape(-1).view(np.uint8)[3] ^= 0x40
        assert tier.verify_readmit(b"key-a") is None
        assert b"key-a" not in tier
        assert tier.bytes_used == 0
        assert tier.stats()["tier_corrupt_dropped"] == 1
        tier.check_invariants()

    def test_digest_binds_dtype_and_shape(self):
        """tier_digest covers dtype and shape, not just raw bytes — a
        reinterpreted payload cannot pass verification."""
        from tnn_tpu.serving.kv_tier import tier_digest

        arr = np.arange(8, dtype=np.float32)
        base = tier_digest(b"k", (arr,))
        assert tier_digest(b"k", (arr.reshape(2, 4),)) != base
        assert tier_digest(b"k", (arr.view(np.int32),)) != base
        assert tier_digest(b"other", (arr,)) != base
        assert tier_digest(b"k", (arr.copy(),)) == base

    def test_lru_bound_displaces_oldest(self):
        leaves = self._leaves(3)
        per = sum(x.nbytes for x in leaves)
        tier = HostKVTier(per * 2)      # room for exactly two entries
        assert tier.demote(b"a", leaves)
        assert tier.demote(b"b", leaves)
        assert tier.demote(b"c", leaves)   # displaces "a" (LRU-oldest)
        assert tier.keys() == [b"b", b"c"]
        assert tier.bytes_used == per * 2
        assert tier.stats()["tier_evictions"] == 1
        tier.check_invariants()

    def test_oversize_entry_degrades_to_plain_eviction(self):
        leaves = self._leaves(4)
        tier = HostKVTier(sum(x.nbytes for x in leaves) - 1)
        assert not tier.demote(b"big", leaves)
        assert len(tier) == 0 and tier.bytes_used == 0
        assert tier.stats()["tier_demote_failures"] == 1
        tier.check_invariants()

    def test_redemote_same_key_replaces_exactly(self):
        tier = HostKVTier(1 << 20)
        old, new = self._leaves(5), self._leaves(6)
        tier.demote(b"k", old)
        tier.demote(b"k", new)           # re-published prefix: newest wins
        assert len(tier) == 1
        assert tier.bytes_used == sum(x.nbytes for x in new)
        out = tier.verify_readmit(b"k")
        np.testing.assert_array_equal(out[0], new[0])
        tier.check_invariants()

    def test_demote_fail_fault_degrades(self):
        plan = FaultPlan(tier_demote_fail_calls=(1,))
        tier = HostKVTier(1 << 20, fault_plan=plan)
        leaves = self._leaves(7)
        assert not tier.demote(b"a", leaves)   # injected: plain eviction
        assert tier.demote(b"b", leaves)       # call 2 passes
        assert plan.fired["tier.demote_fail"] == 1
        assert tier.stats()["tier_demote_failures"] == 1
        assert len(tier) == 1

    def test_corrupt_fault_caught_by_digest(self):
        """The injected corruption flips a byte of a COPY and keeps the
        stored digest — so the verifier genuinely detects it, the same
        code path real bit rot takes."""
        plan = FaultPlan(tier_corrupt_calls=(1,))
        tier = HostKVTier(1 << 20, fault_plan=plan)
        leaves = self._leaves(8)
        tier.demote(b"k", leaves)
        assert tier.verify_readmit(b"k") is None
        assert plan.fired["tier.corrupt"] == 1
        assert tier.stats()["tier_corrupt_dropped"] == 1
        assert b"k" not in tier and tier.bytes_used == 0
        tier.check_invariants()

    def test_slow_readmit_stalls_but_succeeds(self):
        plan = FaultPlan(tier_slow_readmit_calls=(1,),
                         tier_slow_readmit_s=0.02)
        tier = HostKVTier(1 << 20, fault_plan=plan)
        leaves = self._leaves(9)
        tier.demote(b"k", leaves)
        t0 = time.perf_counter()
        out = tier.verify_readmit(b"k")
        assert time.perf_counter() - t0 >= 0.02
        assert out is not None             # late, not wrong
        np.testing.assert_array_equal(out[0], leaves[0])
        assert plan.fired["tier.slow_readmit"] == 1

    def test_int8_leaves_halve_footprint(self):
        shape = (2, 4, 8)
        f32 = (np.zeros(shape, np.float32), np.zeros(shape, np.float32))
        q = (np.zeros(shape, np.int8), np.zeros((2, 4, 1), np.float32),
             np.zeros(shape, np.int8), np.zeros((2, 4, 1), np.float32))
        tier = HostKVTier(1 << 20)
        tier.demote(b"f32", f32)
        f32_bytes = tier.bytes_used
        tier.clear()
        tier.demote(b"int8", q)
        assert tier.bytes_used < f32_bytes * 0.6

    def test_clear_drops_everything(self):
        tier = HostKVTier(1 << 20)
        tier.demote(b"a", self._leaves(10))
        tier.demote(b"b", self._leaves(11))
        tier.clear()
        assert len(tier) == 0 and tier.bytes_used == 0
        assert tier.verify_readmit(b"a") is None
        tier.check_invariants()

    def test_validation(self):
        with pytest.raises(ValueError, match="max_bytes"):
            HostKVTier(0)


class TestTierEngine:
    """Tier <-> engine integration: demotion under pool pressure, verified
    re-admission through the revive path, token-exactness with the full
    feature stack composed, corrupt entries degrading to uncached misses."""

    def _prompts(self, n=6, prefix_len=8, tail_len=4, seed=0):
        """Prompts sharing a cyclic prefix (spec-friendly) + unique tails."""
        rng = np.random.default_rng(seed)
        motif = rng.integers(0, 128, 4)
        prefix = np.tile(motif, prefix_len // 4).astype(np.int32)
        return [np.concatenate([prefix,
                                rng.integers(0, 128, tail_len)
                                   .astype(np.int32)])
                for _ in range(n)]

    def _engine(self, lm, *, tier_bytes, **kw):
        model, params = lm
        merged = dict(num_blocks=10, block_size=4, max_batch_size=2,
                      max_seq_len=32, chunk_size=8,
                      host_tier_bytes=tier_bytes)
        merged.update(kw)
        return InferenceEngine(model, params, **merged)

    def _serve_serially(self, eng, prompts, max_new=6):
        """One request at a time: each finish releases evictable blocks,
        each next admission's alloc pressure demotes them — the working
        set cycles through the tier instead of fitting in the pool."""
        out = []
        for p in prompts:
            rid = eng.submit(p, max_new)
            res = eng.run_until_complete()
            out.append(res[rid])
            del eng.requests[rid]
        return out

    @pytest.mark.slow
    @pytest.mark.parametrize("family", FAMILIES)
    def test_tier_token_exact_composed(self, lm, family):
        """The acceptance gate: tier-on output must equal tier-off output
        token-for-token with prefix cache + ngram speculation + overlap +
        int8 KV all composed, for both model families — and the tier must
        have genuinely carried traffic (demotions and readmits observed),
        while the tier-off twin saw none."""
        prompts = self._prompts()
        compose = dict(spec="ngram", spec_k=3, overlap=True, kv_dtype="int8")
        on = self._engine(lm, tier_bytes=1 << 20, **compose)
        off = self._engine(lm, tier_bytes=0, **compose)
        # two passes: the first populates device cache + tier, the second
        # readmits what pool pressure demoted
        on_toks = [self._serve_serially(on, prompts) for _ in range(2)][1]
        off_toks = [self._serve_serially(off, prompts) for _ in range(2)][1]
        assert on_toks == off_toks
        s_on, s_off = on.stats(), off.stats()
        assert s_on["tier_demotions"] > 0, "pool pressure never demoted"
        assert s_on["tier_readmits"] > 0, "no prefix hit readmitted"
        assert s_on["tier_corrupt_dropped"] == 0
        assert s_off["tier_readmits"] == 0
        _assert_drained(on)
        _assert_drained(off)
        on.check_invariants()

    def test_tier_metrics_and_gauges_flow(self, tiny_lm):
        """stats() and health_gauges() surface the tier counters the
        dashboards scrape."""
        eng = self._engine(tiny_lm, tier_bytes=1 << 20)
        prompts = self._prompts(seed=1)
        self._serve_serially(eng, prompts)
        self._serve_serially(eng, prompts)
        s = eng.stats()
        assert s["host_tier_enabled"]
        assert s["tier_demotions"] > 0
        assert s["tier_bytes"] <= s["tier_max_bytes"] == 1 << 20
        m = eng.metrics.summary()
        assert m["tier_hits"] >= s["tier_readmits"] > 0
        assert m["tier_corrupt"] == 0
        assert m["tier_blocks"] == s["tier_blocks"]
        assert m["tier_bytes"] == s["tier_bytes"]
        # the Prometheus scrape surface carries the tier families
        from tnn_tpu.serving.metrics import render_prometheus

        text = render_prometheus(eng.metrics.prometheus_series())
        for name in ("tnn_serve_tier_blocks", "tnn_serve_tier_bytes",
                     "tnn_serve_tier_hits_total",
                     "tnn_serve_tier_corrupt_total", "tnn_serve_replicas"):
            assert name in text, f"{name} missing from exposition"

    @pytest.mark.slow
    def test_planted_corruption_degrades_to_uncached_miss(self, tiny_lm):
        """A seeded tier.corrupt on the first readmit: the digest check
        drops the entry, the request recomputes the prefix (uncached
        miss), the output stays token-exact, and the corruption counter
        fires — wrong KV is never adopted."""
        plan = FaultPlan(tier_corrupt_calls=(1,))
        eng = self._engine(tiny_lm, tier_bytes=1 << 20, faults=plan)
        ref = self._engine(tiny_lm, tier_bytes=0)
        prompts = self._prompts(seed=2)
        self._serve_serially(eng, prompts)
        got = self._serve_serially(eng, prompts)
        self._serve_serially(ref, prompts)
        want = self._serve_serially(ref, prompts)
        assert got == want
        assert plan.fired["tier.corrupt"] == 1
        s = eng.stats()
        assert s["tier_corrupt_dropped"] == 1
        assert eng.metrics.tier_corrupt == 1
        _assert_drained(eng)

    @pytest.mark.slow
    def test_tier_cleared_on_crash_recovery(self, tiny_lm):
        """Crash recovery re-zeroes the pool; everything demoted before
        the crash is conservatively untrusted and the tier must come back
        empty — stale KV may never survive a restart."""
        plan = FaultPlan(step_crash_calls=(6,))
        eng = self._engine(tiny_lm, tier_bytes=1 << 20, faults=plan)
        model, params = tiny_lm
        prompts = self._prompts(seed=3)
        refs = [_greedy_ref(model, params, p, 4, eng.assembly_len)
                for p in prompts]
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               restart_backoff_s=0.0, max_restarts=2)
        rids = [sup.submit(p, 4) for p in prompts]
        sup.run_sync()
        assert sup.restarts == 1
        assert len(eng.kv_tier) == 0 or eng.stats()["tier_demotions"] > 0
        term = {e["id"]: e for e in events if e["event"] != "token"}
        for rid, r in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == r
        _assert_drained(eng)


class TestElasticFleet:
    """Router join/retire primitives: live scale-up, zero-loss scale-down
    with proactive token-exact migration, injected join failures."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)

    def _sup(self, tiny_lm, **ekw):
        model, params = tiny_lm
        kw = dict(self.KW)
        kw.update(ekw)
        return EngineSupervisor(InferenceEngine(model, params, **kw),
                                restart_backoff_s=0.0)

    def _router(self, tiny_lm, n=2, *, faults=None):
        sups = [self._sup(tiny_lm) for _ in range(n)]
        events = []
        router = Router(sups, event_sink=events.append, seed=0,
                        faults=faults)
        return router, sups, events

    @pytest.mark.slow
    def test_add_replica_joins_and_serves(self, tiny_lm):
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=1)
        rng = np.random.default_rng(30)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 5,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 5) for p in prompts[:2]]
        router.pump(2)
        idx = router.add_replica(lambda: self._sup(tiny_lm))
        assert idx == 1 and router.num_active_replicas() == 2
        gids += [router.submit(p, 5) for p in prompts[2:]]
        # join-shortest-queue places new work on the (empty) joiner
        assert len(router.replicas[1].live) > 0
        router.run_sync()
        term = {e["id"]: e for e in events if e["event"] != "token"}
        for gid, ref in zip(gids, refs):
            assert term[gid]["event"] == "done"
            assert term[gid]["tokens"] == ref
        assert len(router.stats()["replicas"]) == 2
        for h in router.replicas:
            assert h.sup.engine.pool.num_allocated == 0

    def test_join_fail_raises_and_leaves_fleet_intact(self, tiny_lm):
        plan = FaultPlan(scale_join_fail_calls=(1,))
        router, sups, events = self._router(tiny_lm, n=1, faults=plan)
        built = []
        with pytest.raises(ConnectionError):
            router.add_replica(lambda: built.append(1) or
                               self._sup(tiny_lm))
        assert built == [], "join fault fired AFTER the factory ran"
        assert router.num_active_replicas() == 1
        assert plan.fired["scale.join_fail"] == 1
        # the next attempt (site passes) succeeds
        assert router.add_replica(lambda: self._sup(tiny_lm)) == 1
        assert router.num_active_replicas() == 2

    @pytest.mark.slow
    def test_retire_migrates_live_streams_token_exact(self, tiny_lm):
        """The zero-loss scale-down gate: a replica with streams
        mid-decode retires; every stream finishes token-exact with
        exactly one terminal event, nothing is dropped, and the retired
        replica takes no further placements."""
        model, params = tiny_lm
        router, sups, events = self._router(tiny_lm, n=2)
        rng = np.random.default_rng(31)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 6, 7, 8)]
        refs = [_greedy_ref(model, params, p, 8,
                            sups[0].engine.assembly_len) for p in prompts]
        gids = [router.submit(p, 8) for p in prompts]
        router.pump(3)                   # streams genuinely mid-flight
        victim = max(router.replicas, key=lambda h: len(h.live)).idx
        assert len(router.replicas[victim].live) > 0
        assert router.retire_replica(victim)
        assert router.num_active_replicas() == 1
        # retired replicas take no new placements
        extra = router.submit(prompts[0], 8)
        assert extra not in router.replicas[victim].live
        router.run_sync()
        term = {}
        for e in events:
            if e["event"] != "token":
                term.setdefault(e["id"], []).append(e)
        assert sorted(term) == sorted(gids + [extra])
        assert all(len(v) == 1 for v in term.values()), \
            "a migrated stream double-terminated"
        for gid, ref in zip(gids, refs):
            assert term[gid][0]["event"] == "done"
            assert term[gid][0]["tokens"] == ref
            streamed = [e["token"] for e in events
                        if e["event"] == "token" and e["id"] == gid]
            assert streamed == ref
        assert router.metrics.proactive_migrations > 0
        assert router.stats()["replicas"][victim]["retired"]
        for h in router.replicas:
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()

    def test_retire_refuses_last_replica(self, tiny_lm):
        router, sups, events = self._router(tiny_lm, n=2)
        assert router.retire_replica(0)
        assert not router.retire_replica(1), \
            "retired the last replica standing"
        assert not router.retire_replica(0)   # already retired: False
        assert router.num_active_replicas() == 1
        router.run_sync()


class _StubRouter:
    """Duck-typed router for deterministic Autoscaler control-law tests:
    load and TTFT are set directly, actions mutate counters."""

    def __init__(self, active=1, open_requests=0):
        self.active = active
        self.open_requests = open_requests
        self.draining = False
        self.finished = False
        self.p95 = None
        self.adds = 0
        self.retires = 0
        self.fail_joins = 0

    def num_active_replicas(self):
        return self.active

    def ttft_quantile(self, q):
        return self.p95

    def add_replica(self, factory):
        if self.fail_joins > 0:
            self.fail_joins -= 1
            raise ConnectionError("injected join failure")
        factory()
        self.active += 1
        self.adds += 1
        return self.active - 1

    def retire_replica(self, idx, reason="scale-down"):
        if self.active <= 1:
            return False
        self.active -= 1
        self.retires += 1
        return True

    def replica_load(self):
        return {i: i for i in range(self.active)}


class TestAutoscaler:
    """Control-law unit tests on the stub router with an injected clock:
    thresholds, hysteresis, cooldown, bounds, bounded join retry."""

    def _scaler(self, router, **kw):
        merged = dict(min_replicas=1, max_replicas=4, up_load=4.0,
                      down_load=1.0, hysteresis_s=1.0, cooldown_s=2.0,
                      join_retries=2)
        merged.update(kw)
        return Autoscaler(router, lambda: object(), **merged)

    def test_validation(self):
        r = _StubRouter()
        with pytest.raises(ValueError, match="min_replicas"):
            self._scaler(r, min_replicas=0)
        with pytest.raises(ValueError, match="max_replicas"):
            self._scaler(r, min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="dead band"):
            self._scaler(r, up_load=1.0, down_load=1.0)
        with pytest.raises(ValueError, match="slo_ttft_s"):
            self._scaler(r, slo_ttft_s=0.0)
        with pytest.raises(ValueError, match="join_retries"):
            self._scaler(r, join_retries=-1)
        with pytest.raises(ValueError, match="interval_s"):
            self._scaler(r, interval_s=0.0)

    def test_scale_up_on_load_and_cooldown_locks(self):
        r = _StubRouter(active=1, open_requests=10)   # load 10 > 4
        s = self._scaler(r, cooldown_s=2.0)
        assert s.tick(now=0.0) == "up" and r.active == 2
        r.open_requests = 20                          # still way over
        assert s.tick(now=1.0) is None, "cooldown did not lock scale-up"
        assert s.tick(now=2.5) == "up" and r.active == 3
        assert s.stats()["scale_ups"] == 2

    def test_max_replicas_bounds_scale_up(self):
        r = _StubRouter(active=2, open_requests=100)
        s = self._scaler(r, max_replicas=2)
        assert s.tick(now=0.0) is None
        assert r.adds == 0

    def test_hysteresis_requires_sustained_low(self):
        r = _StubRouter(active=3, open_requests=0)    # load 0 < 1
        s = self._scaler(r, hysteresis_s=1.0, cooldown_s=0.0)
        assert s.tick(now=0.0) is None                # starts the timer
        assert s.tick(now=0.9) is None                # not sustained yet
        assert s.tick(now=1.0) == "down" and r.active == 2
        assert s.stats()["scale_downs"] == 1

    def test_dead_band_resets_hysteresis_timer(self):
        r = _StubRouter(active=3, open_requests=0)
        s = self._scaler(r, hysteresis_s=1.0, cooldown_s=0.0)
        assert s.tick(now=0.0) is None                # low: timer starts
        r.open_requests = 6                           # load 2: dead band
        assert s.tick(now=0.5) is None                # timer must reset
        r.open_requests = 0
        assert s.tick(now=1.1) is None, \
            "a dead-band excursion did not reset the hysteresis timer"
        assert s.tick(now=2.1) == "down"

    def test_high_load_resets_hysteresis_timer(self):
        r = _StubRouter(active=3, open_requests=0)
        s = self._scaler(r, hysteresis_s=1.0, cooldown_s=0.0,
                         max_replicas=3)
        assert s.tick(now=0.0) is None
        r.open_requests = 30                          # spike: load 10
        assert s.tick(now=0.5) is None                # at max: no up
        r.open_requests = 0
        assert s.tick(now=1.1) is None, \
            "a load spike did not reset the hysteresis timer"

    def test_min_replicas_bounds_scale_down(self):
        r = _StubRouter(active=1, open_requests=0)
        s = self._scaler(r, hysteresis_s=0.0, cooldown_s=0.0)
        assert s.tick(now=0.0) is None
        assert s.tick(now=10.0) is None
        assert r.retires == 0

    def test_slo_breach_scales_up_at_moderate_load(self):
        r = _StubRouter(active=1, open_requests=2)    # load 2: dead band
        r.p95 = 0.5
        s = self._scaler(r, slo_ttft_s=0.25)
        assert s.tick(now=0.0) == "up", \
            "a TTFT SLO breach must scale up even inside the load band"
        r.p95 = 0.1
        r.open_requests = 2
        assert s.tick(now=10.0) is None               # SLO healthy again

    def test_join_retry_is_bounded(self):
        r = _StubRouter(active=1, open_requests=10)
        r.fail_joins = 10
        s = self._scaler(r, join_retries=2, cooldown_s=0.0)
        assert s.tick(now=0.0) is None
        assert s.stats()["join_failures"] == 3        # 1 try + 2 retries
        assert r.fail_joins == 7, "retry loop was not bounded"
        assert r.adds == 0
        # a failed scale-up must NOT start the cooldown: the next tick
        # (faults cleared) succeeds immediately
        r.fail_joins = 0
        assert s.tick(now=0.0) == "up"

    def test_draining_router_is_left_alone(self):
        r = _StubRouter(active=1, open_requests=100)
        r.draining = True
        s = self._scaler(r)
        assert s.tick(now=0.0) is None and r.adds == 0

    def test_collapsed_fleet_is_left_alone(self):
        r = _StubRouter(active=0, open_requests=5)
        s = self._scaler(r)
        assert s.tick(now=0.0) is None

    def test_victim_is_least_loaded(self):
        seen = []
        r = _StubRouter(active=3, open_requests=0)
        r.retire_replica = lambda idx, reason="scale-down": \
            seen.append(idx) or True
        s = self._scaler(r, hysteresis_s=0.0, cooldown_s=0.0)
        assert s.tick(now=0.0) == "down"
        assert seen == [0], "did not pick the least-loaded replica"

    def test_thread_driver_start_stop(self, tiny_lm):
        r = _StubRouter(active=1, open_requests=0)
        s = self._scaler(r, interval_s=0.01)
        assert s.start() is s
        with pytest.raises(RuntimeError, match="already started"):
            s.start()
        deadline = time.monotonic() + 2.0
        while s.ticks == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        s.stop()
        assert s.ticks > 0
        s.stop()                       # idempotent


@pytest.mark.slow
def test_spike_soak_elastic_fleet(tiny_lm):
    """The elastic-fleet soak gate: a Poisson burst over a 1-replica
    fleet with an autoscaler (deterministic injected clock), tier demote
    faults inside every replica, and one replica hard-killed mid-scale-up.
    Asserts the full contract: exactly one terminal event per admitted
    request, finished streams token-exact against the fault-free
    reference, the scaler actually grew the fleet, and zero leaked blocks
    in every surviving device pool AND every host tier."""
    model, params = tiny_lm
    rng = np.random.default_rng(40)
    uniq = [rng.integers(0, 128, int(n)).astype(np.int32)
            for n in rng.integers(4, 14, 8)]
    max_new = 6
    built = []

    def make_sup(i):
        plan = FaultPlan(seed=200 + i, tier_demote_fail_prob=0.1,
                         tier_corrupt_prob=0.1)
        eng = InferenceEngine(model, params, num_blocks=16, block_size=4,
                              max_batch_size=4, max_seq_len=32,
                              max_queue_depth=24, chunk_size=8,
                              host_tier_bytes=1 << 20, faults=plan)
        sup = EngineSupervisor(eng, restart_backoff_s=0.0, max_restarts=5)
        built.append(sup)
        return sup

    refs = {}
    probe = InferenceEngine(model, params, num_blocks=16, block_size=4,
                            max_batch_size=4, max_seq_len=32)
    for i, p in enumerate(uniq):
        refs[i] = _greedy_ref(model, params, p, max_new,
                              probe.assembly_len)
    events = []
    router = Router([make_sup(0)], event_sink=events.append, seed=4)
    scaler = Autoscaler(router, lambda: make_sup(len(built)),
                        min_replicas=1, max_replicas=3,
                        up_load=2.0, down_load=0.5,
                        hysteresis_s=0.3, cooldown_s=0.1, join_retries=2)
    n_requests, rejected, submitted = 90, 0, {}
    victim = None
    clock = 0.0
    for i in range(n_requests):
        # Poisson arrivals: burst in the middle third, trickle elsewhere
        lam = 3.0 if n_requests // 3 <= i < 2 * n_requests // 3 else 0.5
        for _ in range(max(1, int(rng.poisson(lam)))):
            which = int(rng.integers(0, len(uniq)))
            try:
                gid = router.submit(uniq[which], max_new, priority=i % 3)
                submitted[gid] = which
            except (AdmissionRejected, ShuttingDown, ConnectionError):
                rejected += 1
        router.pump(1)
        clock += 0.05
        scaler.tick(now=clock)
        # hard-kill a grown replica mid-run, once, while work is live
        if victim is None and scaler.ups > 0 and i > n_requests // 2:
            alive = [h for h in router.replicas
                     if not h.killed and not h.retired]
            if len(alive) > 1:
                victim = max(alive, key=lambda h: len(h.live)).idx
                router.kill_replica(victim)
    router.run_sync()
    router.request_drain("soak complete")
    router.run_sync()

    assert scaler.ups >= 1, "the burst never scaled the fleet up"
    assert victim is not None, "no grown replica was ever killed"
    assert router.state is SupervisorState.STOPPED
    assert router.exit_code == 0
    # exactly one terminal event per admitted request
    terminals = [e for e in events if e["event"] != "token"]
    per_gid = {}
    for e in terminals:
        per_gid[e["id"]] = per_gid.get(e["id"], 0) + 1
    assert sorted(per_gid) == sorted(submitted)
    assert all(c == 1 for c in per_gid.values()), per_gid
    # finished streams token-exact against the fault-free reference
    finished = [e for e in terminals if e["event"] == "done"]
    assert finished, "spike soak finished nothing"
    for e in finished:
        assert e["tokens"] == refs[submitted[e["id"]]], \
            f"gid {e['id']} diverged from fault-free reference"
    # tier demote faults genuinely exercised the degradation paths
    fired_demote = sum(s.engine.faults.fired["tier.demote_fail"]
                      for s in built)
    assert fired_demote > 0 or sum(
        s.engine.stats()["tier_demotions"] for s in built) > 0
    # zero leaks: every surviving device pool empty, every tier's byte
    # accounting exact and within bound (the killed replica's pool was
    # torn down with it)
    for h in router.replicas:
        if h.idx != victim:
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()   # includes the tier's
        if h.sup.engine.kv_tier is not None:
            h.sup.engine.kv_tier.check_invariants()


# -- disaggregated prefill/decode serving -------------------------------------


class TestDisagg:
    """Prefill/decode disaggregation through the deterministic sync
    harness: role placement, the first-token boundary handoff (real KV
    wire transfer and the recompute-resume baseline), chaos degradation
    (corrupt/slow wire blocks, a receiver dying mid-adopt), and the
    fleet-wide shared prefix cache."""

    KW = dict(num_blocks=64, block_size=4, max_batch_size=4, max_seq_len=64,
              chunk_size=8, prefix_cache=True)
    THRESH = 16

    def _fleet(self, lm, n=2, *, plans=None, router_kw=None,
               engine_kw=None, sup_kw=None):
        model, params = lm
        ekw = dict(self.KW)
        ekw.update(engine_kw or {})
        skw = dict(restart_backoff_s=0.0)
        skw.update(sup_kw or {})
        plans = plans or [None] * n
        sups = [EngineSupervisor(
                    InferenceEngine(model, params, faults=plans[i], **ekw),
                    **skw)
                for i in range(n)]
        rkw = dict(roles=["prefill"] + ["decode"] * (n - 1),
                   disagg_prompt_threshold=self.THRESH)
        rkw.update(router_kw or {})
        events = []
        router = Router(sups, event_sink=events.append, seed=0, **rkw)
        return router, sups, events

    def _long(self, rng, max_new=6):
        return rng.integers(0, 128, self.THRESH + 8).astype(np.int32), max_new

    @staticmethod
    def _terminals(events):
        return [e for e in events if e["event"] != "token"]

    def _no_leaks(self, router, skip=()):
        for h in router.replicas:
            if h.idx in skip:
                continue
            assert h.sup.engine.pool.num_allocated == 0
            h.sup.engine.check_invariants()

    def test_roles_validation(self, tiny_lm):
        model, params = tiny_lm
        sups = [EngineSupervisor(InferenceEngine(model, params, **self.KW),
                                 restart_backoff_s=0.0) for _ in range(2)]
        with pytest.raises(ValueError, match="every replica"):
            Router(sups, roles=["prefill"])
        with pytest.raises(ValueError, match="unknown replica role"):
            Router(sups, roles=["prefill", "gpu"])
        with pytest.raises(ValueError, match="at least one decode"):
            Router(sups, roles=["prefill", "prefill"])

    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize("kv", [True, False])
    def test_boundary_handoff_token_exact(self, lm, family, kv):
        """The tentpole, both model families: a long prompt lands on the
        prefill replica, crosses to the decode replica at the first-token
        boundary (KV wire transfer or recompute-resume), and the client
        sees one uninterrupted token-exact stream."""
        model, params = lm
        router, sups, events = self._fleet(
            lm, router_kw=dict(handoff_kv=kv))
        rng = np.random.default_rng(11)
        lp, ln = self._long(rng)
        sp = rng.integers(0, 128, 6).astype(np.int32)
        alen = sups[0].engine.assembly_len
        refs = [_greedy_ref(model, params, lp, ln, alen),
                _greedy_ref(model, params, sp, 5, alen)]
        glong = router.submit(lp, ln)
        gshort = router.submit(sp, 5)
        # role placement: the long prompt prefers the prefill replica,
        # the short one the decode replica
        assert glong in router.replicas[0].live
        assert gshort in router.replicas[1].live
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[glong]["event"] == "done"
        assert term[glong]["tokens"] == refs[0]
        assert term[gshort]["tokens"] == refs[1]
        streamed = [e["token"] for e in events
                    if e["event"] == "token" and e["id"] == glong]
        assert streamed == refs[0]     # no token duplicated or dropped
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        recv = sups[1].engine.metrics.summary()
        if kv:
            assert st["handoff_fallbacks"] == 0
            assert recv["handoff_adopted_blocks"] > 0
            # the resume prefill hit the adopted blocks instead of
            # recomputing them
            assert recv["prefill_tokens_saved"] > 0
        else:
            assert recv["handoff_adopted_blocks"] == 0
        self._no_leaks(router)

    def test_boundary_handoff_overlap_single_chunk_ships_kv(self, tiny_lm):
        """Overlap defers prefix publishes to idle time; a single-chunk
        long prompt commits its whole chain AND its first token in the
        same tick, so the boundary export races the deferred publish and
        (before the fix) found nothing resident — every handoff silently
        degraded to recompute-resume. export_prefix now drains the
        deferred queue first; the wire must actually ship."""
        model, params = tiny_lm
        router, sups, events = self._fleet(
            tiny_lm, router_kw=dict(handoff_kv=True),
            engine_kw=dict(overlap=True, chunk_size=64))
        rng = np.random.default_rng(23)
        lp, ln = self._long(rng)
        alen = sups[0].engine.assembly_len
        ref = _greedy_ref(model, params, lp, ln, alen)
        g = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[g]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        assert st["handoff_fallbacks"] == 0, \
            "single-chunk overlap handoff degraded: export raced the " \
            "deferred publish"
        recv = sups[1].engine.metrics.summary()
        # the FULL chain crossed: every complete prompt block adopted
        assert recv["handoff_adopted_blocks"] == len(lp) // 4
        assert recv["prefill_tokens_saved"] > 0
        self._no_leaks(router)

    def test_corrupt_wire_block_degrades_to_recompute(self, tiny_lm):
        """handoff.corrupt chaos: the receiver's digest check catches the
        damage, adopts nothing, and the handoff falls back to token-exact
        recompute-resume — never a wrong token."""
        model, params = tiny_lm
        plans = [None, FaultPlan(handoff_corrupt_calls=(1,))]
        router, sups, events = self._fleet(tiny_lm, plans=plans)
        rng = np.random.default_rng(12)
        lp, ln = self._long(rng)
        ref = _greedy_ref(model, params, lp, ln, sups[0].engine.assembly_len)
        gid = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        assert st["handoff_fallbacks"] == 1
        recv = sups[1].engine.metrics.summary()
        assert recv["handoff_corrupt"] == 1
        assert recv["handoff_adopted_blocks"] == 0
        self._no_leaks(router)

    def test_slow_wire_adopt_is_late_not_wrong(self, tiny_lm):
        """handoff.slow chaos: a congested transfer stalls the adopt but
        does not fail it — the blocks still land, verified."""
        model, params = tiny_lm
        plans = [None, FaultPlan(handoff_slow_calls=(1,),
                                 handoff_slow_s=0.005)]
        router, sups, events = self._fleet(tiny_lm, plans=plans)
        rng = np.random.default_rng(13)
        lp, ln = self._long(rng)
        ref = _greedy_ref(model, params, lp, ln, sups[0].engine.assembly_len)
        gid = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        assert st["handoff_fallbacks"] == 0
        assert sups[1].engine.faults.fired["handoff.slow"] == 1
        assert sups[1].engine.metrics.summary()["handoff_adopted_blocks"] > 0
        self._no_leaks(router)

    def test_receiver_pool_pressure_degrades(self, tiny_lm):
        """A full receiver pool ends the adopt walk early (here: at zero
        blocks, via an injected alloc failure) — handoff still happens,
        as recompute-resume."""
        model, params = tiny_lm
        plans = [None, FaultPlan(alloc_fail_calls=(1,))]
        router, sups, events = self._fleet(tiny_lm, plans=plans)
        rng = np.random.default_rng(14)
        lp, ln = self._long(rng)
        ref = _greedy_ref(model, params, lp, ln, sups[0].engine.assembly_len)
        gid = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        assert st["handoff_fallbacks"] == 1
        assert sups[1].engine.metrics.summary()[
            "handoff_adopted_blocks"] == 0
        self._no_leaks(router)

    def test_no_decode_target_finishes_in_place(self, tiny_lm):
        """Roles are preferences, never admission gates: with every decode
        replica dead, the long prompt finishes on the prefill replica."""
        model, params = tiny_lm
        router, sups, events = self._fleet(tiny_lm)
        router.kill_replica(1)
        rng = np.random.default_rng(15)
        lp, ln = self._long(rng)
        ref = _greedy_ref(model, params, lp, ln, sups[0].engine.assembly_len)
        gid = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 0
        assert st["handoff_fallbacks"] == 0
        self._no_leaks(router, skip=(1,))

    def test_receiver_killed_mid_adopt_degrades(self, tiny_lm, monkeypatch):
        """The receiver dies DURING the adopt call: the handoff degrades
        to recompute-resume on a surviving replica — never a dropped
        request."""
        model, params = tiny_lm
        router, sups, events = self._fleet(tiny_lm)
        rng = np.random.default_rng(16)
        lp, ln = self._long(rng)
        ref = _greedy_ref(model, params, lp, ln, sups[0].engine.assembly_len)

        def dying_adopt(exports):
            router.kill_replica(1)
            raise EngineCrash("receiver died mid-adopt")

        monkeypatch.setattr(sups[1], "adopt_prefix", dying_adopt)
        gid = router.submit(lp, ln)
        router.run_sync()
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[gid]["event"] == "done" and term[gid]["tokens"] == ref
        st = router.stats()
        assert st["boundary_handoffs"] == 1
        assert st["handoff_fallbacks"] == 1
        self._no_leaks(router, skip=(1,))

    def test_fleet_prefix_pull_then_local_hit(self, tiny_lm):
        """Fleet-wide shared prefix cache: a prefix published on the
        prefill replica is pulled over on a miss from the decode replica
        (verified wire path), after which the same prefix hits locally —
        no second pull."""
        model, params = tiny_lm
        router, sups, events = self._fleet(
            tiny_lm, router_kw=dict(disagg_prompt_threshold=12,
                                    fleet_prefix=True))
        rng = np.random.default_rng(17)
        prefix = rng.integers(0, 128, 8).astype(np.int32)
        seeder = np.concatenate(
            [prefix, rng.integers(0, 128, 4).astype(np.int32)])
        shorts = [np.concatenate(
            [prefix, rng.integers(0, 128, 3).astype(np.int32)])
            for _ in range(2)]
        alen = sups[0].engine.assembly_len
        g1 = router.submit(seeder, 1)   # 12 tokens -> the prefill replica
        assert g1 in router.replicas[0].live
        router.run_sync()
        router._refresh_prefix_dir()
        g2 = router.submit(shorts[0], 4)  # 11 tokens -> the decode replica
        assert g2 in router.replicas[1].live
        router.run_sync()
        st = router.stats()
        assert st["fleet_prefix_pulls"] == 1
        recv = sups[1].engine.metrics.summary()
        assert recv["prefill_tokens_saved"] >= 8   # two pulled blocks
        # the adopted keys are now local: same prefix, no second pull
        router._refresh_prefix_dir()
        g3 = router.submit(shorts[1], 4)
        router.run_sync()
        assert router.stats()["fleet_prefix_pulls"] == 1
        term = {e["id"]: e for e in self._terminals(events)}
        assert term[g1]["tokens"] == _greedy_ref(model, params, seeder,
                                                 1, alen)
        for g, p in ((g2, shorts[0]), (g3, shorts[1])):
            assert term[g]["event"] == "done"
            assert term[g]["tokens"] == _greedy_ref(model, params, p,
                                                    4, alen)
        self._no_leaks(router)

    def test_auto_roles_assignment(self, tiny_lm):
        """roles="auto": the probe loop dedicates the healthiest half to
        decode and the rest to prefill; a fleet shrunk to one alive
        replica reverts to mixed."""
        router, sups, _ = self._fleet(tiny_lm, n=3,
                                      router_kw=dict(roles="auto"))
        assert [h.role for h in router.replicas] == ["mixed"] * 3
        router._probe()
        roles = [h.role for h in router.replicas]
        assert roles.count("decode") == 2 and roles.count("prefill") == 1
        router.kill_replica(1)
        router.kill_replica(2)
        router._probe()
        assert router.replicas[0].role == "mixed"

    def test_role_singleton_never_ejected(self, tiny_lm):
        """Role-aware ejection: the lone prefill replica is structurally
        slower than its decode peers (it eats every long prompt) — judged
        only against same-role peers, a singleton is never ejected for
        doing its job."""
        router, sups, _ = self._fleet(tiny_lm, n=3)
        # plant a fleet-median-breaking score on the prefill replica: under
        # the old fleet-wide median this ejects; role-aware it must not
        router.replicas[0].health.dispatch_latency_s = 10.0
        for _ in range(3):
            router._update_health()
            time.sleep(0.01)
        assert not router.replicas[0].degraded
        assert router.stats()["degraded_ejections"] == 0
        # ... and an ejection stranded in a group of one heals: plant the
        # degraded state a pre-role-aware run could have left behind
        router.replicas[0].degraded = True
        router._update_health()
        assert not router.replicas[0].degraded

    def test_handoff_pending_requests_are_never_hedged(self, tiny_lm):
        """Handoff-aware hedging: a long prompt mid-prefill on the prefill
        tier is slow BY SELECTION — the boundary handoff is already its
        migration, so the hedge scan must skip it."""
        router, sups, _ = self._fleet(
            tiny_lm, router_kw=dict(hedge_ttft_s=0.0, hedge_budget=1.0))
        rng = np.random.default_rng(18)
        lp, ln = self._long(rng)
        gid = router.submit(lp, ln)
        rec = router._open[gid]
        assert rec.prefer_role == "prefill"
        # every request is overdue at threshold 0.0 — yet the pending
        # handoff must be exempt
        router._maybe_hedge()
        assert router.stats()["hedges_fired"] == 0
        assert rec.hedge_epoch is None
        router.run_sync()
        assert router.stats()["boundary_handoffs"] == 1
        self._no_leaks(router)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_disagg_composed_chaos_token_exact(self, lm, family):
        """The PR gate, both model families: disagg-on vs disagg-off with
        prefix cache + ngram spec + overlap + int8 KV composed, under
        handoff chaos (seeded corrupt + slow wire blocks, one decode
        replica killed mid-run) — every stream token-exact against the
        greedy reference, zero leaked blocks on the survivors."""
        ekw = dict(kv_dtype="int8", spec="ngram", spec_k=3, overlap=True)
        rng = np.random.default_rng(19)
        prefix = rng.integers(0, 128, 8).astype(np.int32)
        prompts = [rng.integers(0, 128, self.THRESH + 4 + i).astype(np.int32)
                   for i in range(4)]
        prompts += [np.concatenate(
            [prefix, rng.integers(0, 128, 3 + i).astype(np.int32)])
            for i in range(4)]
        max_new = 6

        def run(disagg):
            plans = [None,
                     FaultPlan(seed=3, handoff_corrupt_prob=0.4,
                               handoff_slow_prob=0.4, handoff_slow_s=0.001),
                     FaultPlan(seed=4, handoff_corrupt_prob=0.4,
                               handoff_slow_prob=0.4, handoff_slow_s=0.001)]
            rkw = (dict(roles=["prefill", "decode", "decode"],
                        disagg_prompt_threshold=self.THRESH,
                        handoff_kv=True, fleet_prefix=True)
                   if disagg else dict(roles=None,
                                       disagg_prompt_threshold=0))
            router, sups, events = self._fleet(
                lm, n=3, plans=plans, router_kw=rkw, engine_kw=ekw)
            gids = [router.submit(p, max_new) for p in prompts]
            router.pump(2)
            router.kill_replica(2)       # a receiver dies mid-fleet
            router.run_sync()
            term = {e["id"]: e for e in self._terminals(events)}
            toks = []
            for g in gids:
                assert term[g]["event"] == "done"
                toks.append(term[g]["tokens"])
            self._no_leaks(router, skip=(2,))
            return toks, router.stats(), sups[0].engine.assembly_len

        on_toks, on_st, _ = run(True)
        off_toks, off_st, _ = run(False)
        # the disagg contract is on == off: crossing the prefill/decode
        # boundary (with chaos-degraded KV handoffs in the mix) must not
        # change a single token relative to the same engines un-split.
        # The f32 greedy reference is NOT the baseline here — int8 KV is
        # argmax-sensitive on some prompts, identically on both sides,
        # and that quantization contract is tested elsewhere.
        assert on_toks == off_toks, \
            "disagg-on diverged from the disagg-off twin"
        assert on_st["boundary_handoffs"] >= 1
        assert off_st["boundary_handoffs"] == 0


class TestPrefixCacheAdoptEdges:
    """PrefixCache.adopt (the wire/tier re-admission entry) against the
    races the engine sees in production: duplicate adoption of one chain
    key, and a block reclaimed out from under a just-adopted entry."""

    def test_duplicate_adopt_same_key_loses(self):
        pc = PrefixCache(block_size=4)
        assert pc.adopt(b"k1", 3)
        assert not pc.adopt(b"k1", 9)      # occupied key: first wins
        assert not pc.adopt(b"k2", 3)      # block already serves a chain
        assert pc.block_of(b"k1") == 3 and pc.block_of(b"k2") is None
        assert len(pc) == 1

    def test_adopt_after_concurrent_reclaim(self):
        pc = PrefixCache(block_size=4)
        assert pc.adopt(b"k1", 3)
        pc.drop_blocks([3])                # the pool reclaimed it mid-race
        assert pc.block_of(b"k1") is None and len(pc) == 0
        assert not pc.contains_block(3)
        # the key is free again: a later adopt re-admits under a new block
        assert pc.adopt(b"k1", 7)
        assert pc.block_of(b"k1") == 7


class TestHostTierTPExclusion:
    """The host-RAM KV tier is incompatible with tensor-parallel pool
    sharding (demoted page slices would need a cross-shard gather/
    scatter): both the engine constructor and the CLI must fail fast with
    a clear one-line error, not crash somewhere in kernel wiring."""

    def test_engine_rejects_tier_with_tp(self, tiny_lm):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="tp>1 is unsupported"):
            InferenceEngine(model, params, num_blocks=8, block_size=4,
                            max_batch_size=2, max_seq_len=16,
                            prefix_cache=True,
                            host_tier_bytes=1 << 20, tp=2)

    def test_cli_rejects_tier_with_tp(self, capsys):
        from tnn_tpu.cli import serve as serve_cli
        with pytest.raises(SystemExit):
            serve_cli.main(["--host-tier-bytes", "1048576", "--tp", "2"])
        err = capsys.readouterr().err
        assert "--host-tier-bytes is incompatible with --tp" in err
