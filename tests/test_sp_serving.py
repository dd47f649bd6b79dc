"""Sequence-parallel serving: sp=2 must be TOKEN-EXACT against sp=1.

SP shards each request's KV blocks position-wise over a context mesh:
every shard sweeps its own pages with the ragged paged kernel and the
per-shard partials merge through one online-softmax psum per layer
(ops/softmax_merge.py). The merge itself is exact to float tolerance, so
— exactly like the TP lane — the gate here is byte-exactness of sampled
token streams on fixed seeds: every composition that works at sp=1 (spec
decode, prefix cache, the overlapped loop, int8 KV) must emit identical
tokens at sp=2, through preemption and a mid-run
supervisor crash. The headline capability gate is the long-context one:
a prompt whose KV exceeds a single chip's pool must SERVE at sp=2 and
fail cleanly at sp=1.

Runs on the conftest's 8-device virtual CPU platform; the ``sp`` fixture
skips on real single-chip hosts.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from tnn_tpu.serving import (TERMINAL_STATES, EngineSupervisor, FaultPlan,
                             InferenceEngine, PagedKVPool, PoolExhausted,
                             RequestState, compile_cache)

pytestmark = pytest.mark.sp

KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


def _prompts(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, int(l)).astype(np.int32)
            for l in rng.integers(5, 14, n)]


def _greedy_ref(model, params, prompt, max_new, max_len):
    from tnn_tpu.models.gpt2 import generate

    return np.asarray(generate(model, params, prompt[None], max_new,
                               max_len=max_len))[0].tolist()


def _run(model, params, prompts, max_new=8, stagger=0, **kw):
    merged = dict(KW)
    merged.update(kw)
    eng = InferenceEngine(model, params, **merged)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_new))
        if stagger and i % stagger == stagger - 1:
            eng.step()
    out = eng.run_until_complete()
    return eng, [out[r] for r in rids]


def _assert_drained(eng):
    states = {r.rid: r.state for r in eng.requests.values()}
    assert all(s in TERMINAL_STATES for s in states.values()), states
    assert not eng.has_work
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free + eng.pool.num_evictable == eng.pool.capacity
    eng.check_invariants()


def _shard_devices(eng):
    """The distinct devices actually holding the engine's KV pages."""
    pages = eng.pool.pages_k
    data = pages.data if hasattr(pages, "data") else pages
    return {d for d in data.sharding.device_set}


# -- fail-fast validation -----------------------------------------------------


class TestSPValidation:
    def test_rejects_sp_over_device_count(self, tiny_lm, sp):
        model, params = tiny_lm
        toomany = jax.device_count() + 1
        with pytest.raises(ValueError, match="device"):
            InferenceEngine(model, params, sp=toomany, **KW)

    def test_rejects_sp_with_tp(self, tiny_lm, sp):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="ONE of sp / tp"):
            InferenceEngine(model, params, sp=sp, tp=2, **KW)

    def test_rejects_sp_with_host_tier(self, tiny_lm, sp):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="host"):
            InferenceEngine(model, params, sp=sp, host_tier_bytes=1 << 20,
                            **KW)

    def test_rejects_quant_weights(self, tiny_lm, sp):
        model, params = tiny_lm
        with pytest.raises(ValueError, match="quant"):
            InferenceEngine(model, params, sp=sp, quant_weights=True, **KW)

    def test_rejects_indivisible_num_blocks(self, tiny_lm, sp):
        model, params = tiny_lm
        kw = dict(KW)
        kw["num_blocks"] = 33
        with pytest.raises(ValueError, match="divide"):
            InferenceEngine(model, params, sp=sp, **kw)

    def test_rejects_indivisible_assembly_width(self, tiny_lm, sp):
        """blocks_per_seq %% sp is a pre-flight: an sp=2 engine whose
        max_seq_len rounds to an odd block count dies with a pointed
        message, not a shard_map shape error mid-request."""
        model, params = tiny_lm
        kw = dict(KW)
        kw["max_seq_len"] = 12     # ceil(12 / 4) = 3 blocks, 3 % 2 != 0
        with pytest.raises(ValueError, match="blocks_per_seq"):
            InferenceEngine(model, params, sp=sp, **kw)

    def test_cli_preflight_rejects_sp_with_tp(self, sp, capsys):
        """tnn-serve dies with a pointed one-liner BEFORE touching model
        weights, not a shard_map traceback out of engine construction."""
        from tnn_tpu.cli import serve as serve_cli
        with pytest.raises(SystemExit):
            serve_cli.main(["--sp", str(sp), "--tp", "2"])
        assert "pick ONE of --sp / --tp" in capsys.readouterr().err

    def test_cli_preflight_rejects_sp_with_host_tier(self, sp, capsys):
        from tnn_tpu.cli import serve as serve_cli
        with pytest.raises(SystemExit):
            serve_cli.main(["--sp", str(sp), "--host-tier-bytes", "1048576"])
        err = capsys.readouterr().err
        assert "--host-tier-bytes is incompatible with --sp" in err

    def test_cli_preflight_rejects_indivisible_blocks(self, sp, capsys):
        from tnn_tpu.cli import serve as serve_cli
        with pytest.raises(SystemExit):
            serve_cli.main(["--sp", "3", "--num-blocks", "64"])
        assert "does not divide" in capsys.readouterr().err


# -- pool: round-robin placement and bottleneck capacity ----------------------


class TestSPPool:
    def _pool(self, sp=2, num_blocks=16):
        return PagedKVPool(num_blocks=num_blocks, block_size=4,
                           num_layers=1, num_kv_heads=1, head_dim=4, sp=sp)

    def test_round_robin_ownership(self):
        """Table position j allocates from shard j %% sp, and ownership is
        derivable from the block ID range alone (what shard_tables uses)."""
        pool = self._pool()
        blocks = pool.alloc(6)
        for j, g in enumerate(blocks):
            assert pool.owner(g) == j % 2
        pool.free(blocks)

    def test_num_allocatable_is_bottleneck(self):
        """Aggregate capacity is gated by the SCARCEST shard: admission
        (scheduler budgets consult num_allocatable) must not plan blocks a
        round-robin alloc cannot actually place."""
        pool = self._pool()
        assert pool.capacity == 14              # 16 - one scratch per shard
        held = pool.alloc(4, start=0)           # balanced: 2 + 2
        assert pool.num_allocatable == 10
        skew = [pool.alloc(1, start=0)[0] for _ in range(3)]  # shard 0 only
        assert all(pool.owner(g) == 0 for g in skew)
        # shard 0 has 2 free, shard 1 has 5 -> bottleneck caps at 2 * 2
        assert pool.num_allocatable == 4
        pool.free(held + skew)
        assert pool.num_allocatable == pool.capacity

    def test_exhaustion_names_the_shard(self):
        pool = self._pool(num_blocks=4)         # 1 usable block per shard
        pool.alloc(1, start=0)
        with pytest.raises(PoolExhausted, match="shard"):
            pool.alloc(1, start=0)              # shard 0 is out; shard 1 free

    def test_shard_tables_by_id_range(self):
        from tnn_tpu.serving.step_build import shard_tables

        tables = np.array([[0, 9, 3, 12]], np.int32)    # blocks_per_shard=8
        out = shard_tables(tables, 2, 8)
        assert out.shape == (2, 1, 4)
        np.testing.assert_array_equal(out[0, 0], [0, -1, 3, -1])
        np.testing.assert_array_equal(out[1, 0], [-1, 1, -1, 4])


# -- exactness: sp=2 == sp=1 == offline reference -----------------------------


class TestSPExactness:
    def test_staggered_parity(self, tiny_lm, sp):
        """Staggered admission (ragged offsets): sp=2 streams must equal
        sp=1 streams AND the offline greedy reference, token for token.
        seed=13 is a checked tie-free seed on
        jax 0.9: the merge is exact to float tolerance, but the bf16
        per-shard partials can flip a greedy near-tie of this random
        model."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=13)
        kw = dict(stagger=2)
        eng1, base = _run(model, params, prompts, **kw)
        eng2, sharded = _run(model, params, prompts, sp=sp, **kw)
        assert sharded == base
        for toks, p in zip(sharded, prompts):
            assert toks == _greedy_ref(model, params, p, 8,
                                       eng2.assembly_len)
        assert eng2.stats()["sp_degree"] == sp
        assert len(_shard_devices(eng2)) == sp
        _assert_drained(eng2)

    def test_full_composition_exact(self, tiny_lm, sp):
        """The whole stack at once — int8 KV + ngram spec decode + prefix
        cache + overlapped loop on the paged path — must match the same
        composition at sp=1 exactly (int8 rounding happens at the scatter,
        before sharding, so even the closeness-gated lane is parity)."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=7) + _prompts(2, seed=7)[:1]  # a repeat
        kw = dict(kv_dtype="int8", spec="ngram",
                  prefix_cache=True, overlap=True)
        eng1, base = _run(model, params, prompts, **kw)
        eng2, sharded = _run(model, params, prompts, sp=sp, **kw)
        assert sharded == base
        assert eng2.stats()["kv_dtype"] == "int8"
        _assert_drained(eng2)

    def test_preemption_parity(self, tiny_lm, sp):
        """A starved pool preempts identically under SP: recompute-requeue
        of a sequence-sharded request produces byte-identical output and
        no shard leaks a block."""
        model, params = tiny_lm
        prompts = _prompts(4, seed=1)
        kw = dict(num_blocks=10)
        eng1, base = _run(model, params, prompts, max_new=10, **kw)
        eng2, sharded = _run(model, params, prompts, max_new=10, sp=sp, **kw)
        assert eng2.metrics.preemptions > 0, "pool was never exhausted"
        assert sharded == base
        _assert_drained(eng2)

    def test_sampled_rows_deterministic(self, tiny_lm, sp):
        """Stochastic sampling inside the shard_map body: same seed, same
        tokens as sp=1 (the PRNG key replicates and the merged logits
        agree on this model)."""
        model, params = tiny_lm
        p = np.arange(5, dtype=np.int32)

        def run(**kw):
            eng = InferenceEngine(model, params, seed=3, **KW, **kw)
            g = eng.submit(p, 8)
            s = eng.submit(p, 8, temperature=0.9, top_k=16, top_p=0.9)
            out = eng.run_until_complete()
            return eng, out[g], out[s]

        eng1, g1, s1 = run()
        eng2, g2, s2 = run(sp=sp)
        assert g2 == g1 == _greedy_ref(model, params, p, 8,
                                       eng2.assembly_len)
        assert s2 == s1
        assert all(0 <= t < model.vocab_size for t in s2)


# -- the capability gate: context beyond one chip's pool ----------------------


class TestSPLongContext:
    def test_long_prompt_needs_the_context_mesh(self, tiny_lm, sp):
        """THE reason sp exists: a prompt whose KV exceeds a single chip's
        pool serves at sp=2 (aggregate pool ~ N x) and fails cleanly — a
        pointed admission error, not an OOM or a hang — at sp=1 on the
        same per-chip footprint."""
        model, params = tiny_lm
        long_p = (np.arange(40, dtype=np.int32) * 7 + 3) % 128
        per_chip = dict(num_blocks=8, block_size=4, max_batch_size=2,
                        max_seq_len=64)
        eng1 = InferenceEngine(model, params, **per_chip)
        with pytest.raises(ValueError, match="exceeds"):
            eng1.submit(long_p, 4)
        # same 8-block per-chip footprint, sp=2 -> 16 blocks aggregate
        both = dict(per_chip)
        both["num_blocks"] = 16
        eng2 = InferenceEngine(model, params, sp=sp, **both)
        assert eng2.pool.blocks_per_shard == 8
        r = eng2.submit(long_p, 4)
        out = eng2.run_until_complete()[r]
        assert out == _greedy_ref(model, params, long_p, 4,
                                  eng2.assembly_len)
        _assert_drained(eng2)


# -- failure handling ---------------------------------------------------------


class TestSPFailures:
    def test_supervisor_crash_restart_exact(self, tiny_lm, sp):
        """A mid-run engine crash under SP: the supervisor's restart resets
        the pool — the reset must purge EVERY context-mesh shard's pages —
        and the migrated requests finish token-exact."""
        model, params = tiny_lm
        plan = FaultPlan(step_crash_calls=(2,))
        eng = InferenceEngine(model, params, sp=sp, faults=plan,
                              num_blocks=32,
                              block_size=4, max_batch_size=2, max_seq_len=32)
        events = []
        sup = EngineSupervisor(eng, event_sink=events.append,
                               restart_backoff_s=0.0, max_restarts=2)
        prompts = _prompts(4, seed=9)
        refs = [_greedy_ref(model, params, p, 5, eng.assembly_len)
                for p in prompts]
        rids = [sup.submit(p, 5) for p in prompts]
        sup.run_sync()
        assert sup.restarts == 1
        term = {e["id"]: e for e in events if e["event"] != "token"}
        assert sorted(term) == sorted(rids)
        for rid, ref in zip(rids, refs):
            assert term[rid]["event"] == "done"
            assert term[rid]["tokens"] == ref
        # the reset pool is still block-sharded across all sp devices
        assert len(_shard_devices(eng)) == sp
        _assert_drained(eng)

    def test_chunk_alloc_failure_zero_leaks_per_shard(self, tiny_lm, sp):
        """Injected alloc faults at chunk boundaries and mid-decode: every
        failure path must return a sequence-sharded request's blocks to
        their owning shards — zero leaks on ANY shard, survivors match a
        fault-free run."""
        model, params = tiny_lm
        prompts = _prompts(6, seed=6)
        kw = dict(num_blocks=16, block_size=4, max_batch_size=4,
                  max_seq_len=32, sp=sp)

        def run(plan=None):
            eng = InferenceEngine(model, params, faults=plan, **kw)
            rids = [eng.submit(p, 8) for p in prompts]
            eng.run_until_complete()
            return eng, rids

        ref_eng, ref_rids = run()
        plan = FaultPlan(seed=9, alloc_fail_prob=0.12)
        eng, rids = run(plan)
        assert plan.fired["pool.alloc"] >= 1, "chaos never fired — dead test"
        assert all(eng.result(r).state in TERMINAL_STATES for r in rids)
        for rid, ref_rid in zip(rids, ref_rids):
            if eng.result(rid).state is RequestState.FINISHED:
                assert list(eng.requests[rid].out_tokens) == \
                    list(ref_eng.requests[ref_rid].out_tokens)
        # zero leaks per shard, not just in aggregate
        for s in range(sp):
            assert eng.pool._shard_avail(s) == eng.pool.blocks_per_shard - 1
        _assert_drained(eng)


# -- persistent compilation cache ---------------------------------------------


_CC_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           + " --xla_backend_optimization_level=0")
import numpy as np, jax
from tnn_tpu.serving import InferenceEngine, compile_cache
from tnn_tpu.models.gpt2 import GPT2

cache = compile_cache.enable(sys.argv[1])
before = compile_cache.entry_count(cache)
model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
             num_heads=2)
params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
eng = InferenceEngine(model, params, num_blocks=16, block_size=4,
                      max_batch_size=2, max_seq_len=32)
r = eng.submit(np.arange(7, dtype=np.int32), 6)
out = eng.run_until_complete()[r]
print("CC", before, compile_cache.entry_count(cache), out)
"""


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _no_outside_placement(self, monkeypatch):
        # JAX_COMPILATION_CACHE_DIR outranks the directory these tests pass
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)

    def test_enable_mechanics(self, tmp_path):
        """enable() must defeat JAX's once-only cache initialization (any
        compile before it would otherwise pin the cache off for the whole
        process) and entry_count() must read warmth without jax internals."""
        d = str(tmp_path / "cc")
        assert compile_cache.entry_count(d) == 0    # missing dir == empty
        try:
            cache = compile_cache.enable(d)
            assert compile_cache.active_dir() == cache
            salt = np.float32(os.getpid() % 97)     # a never-seen program
            jax.jit(lambda x: x * salt + 41.5)(
                np.arange(8, dtype=np.float32))
            assert compile_cache.entry_count(cache) > 0
        finally:
            compile_cache.disable()
        assert compile_cache.active_dir() is None

    def test_compile_cache_warm_restart_token_exact(self, tmp_path):
        """The serving story: a process restart against the same cache dir
        re-serves from persisted executables — the warm build adds ZERO new
        entries and emits the exact same tokens. (Two subprocesses because
        that IS the deployment shape — restart / scale-up — and JAX's
        in-process executable reload is not exercised by a live engine.)"""
        d = str(tmp_path / "cc")
        env = dict(os.environ, PYTHONPATH=os.getcwd())

        def launch():
            out = subprocess.run(
                [sys.executable, "-c", _CC_CHILD, d], env=env,
                capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, out.stderr[-2000:]
            line = [l for l in out.stdout.splitlines()
                    if l.startswith("CC ")][-1]
            before, after, toks = line[3:].split(" ", 2)
            return int(before), int(after), toks

        b1, a1, toks1 = launch()
        assert b1 == 0 and a1 > 0, "cold run persisted nothing"
        b2, a2, toks2 = launch()
        assert b2 == a1, "warm run did not see the cold run's entries"
        assert a2 == a1, f"warm run recompiled: {a1} -> {a2} entries"
        assert toks2 == toks1


# -- observability ------------------------------------------------------------


class TestSPObservability:
    def test_gauges_and_exposition(self, tiny_lm, sp):
        model, params = tiny_lm
        eng, _ = _run(model, params, _prompts(2, seed=3), sp=sp)
        s = eng.stats()
        assert s["sp_degree"] == sp
        assert s["pool_blocks_per_shard"] == eng.pool.blocks_per_shard
        assert eng.pool.blocks_per_shard * sp == KW["num_blocks"]
        fams = {f["name"]: f for f in eng.metrics.prometheus_series()}
        fam = fams["tnn_serve_sp_degree"]
        assert fam["type"] == "gauge"
        assert fam["samples"][0][-1] == float(sp)
        assert eng.metrics.summary()["sp_degree"] == sp

    def test_spmerge_span_traced(self, tiny_lm, sp):
        """With tracing on, SP dispatch wraps the step in a serve.spmerge
        span carrying the degree and the per-step merge count (one
        online-softmax psum per layer)."""
        from tnn_tpu.profiling.profiler import Profiler

        model, params = tiny_lm
        prof = Profiler(source="sp-test")
        eng, _ = _run(model, params, _prompts(2, seed=8), sp=sp,
                      profiler=prof, trace=True)
        spans = [e for e in prof.events
                 if e.name.startswith("serve.spmerge")]
        assert spans, "no serve.spmerge span recorded"
        assert f"sp={sp}" in spans[0].name
        assert f"count={model.num_layers}" in spans[0].name
