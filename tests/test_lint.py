"""tnnlint tests: one positive and one negative fixture per rule, the
suppression/baseline machinery, and the repo-wide tier-1 gate.

The fixtures are the executable spec of each contract: the positive shows
the exact anti-pattern the rule exists to catch, the negative shows the
blessed idiom that must stay clean. The gate at the bottom is the real
enforcement: ``tnn_tpu/`` lints to zero findings against an EMPTY baseline,
so any new violation fails tier-1 until it is fixed or suppressed with an
inline justification.
"""
from pathlib import Path

import pytest

from tools.tnnlint import lint_source, lint_paths, rule_registry
from tools.tnnlint.baseline import compare, read_baseline, write_baseline
from tools.tnnlint.cli import main
from tools.tnnlint.config import load_config
from tools.tnnlint.core import BARE_SUPPRESSION

REPO = Path(__file__).resolve().parent.parent


def _rules(src, select):
    return [v.rule for v in lint_source(src, select=[select])]


# -- rule fixtures: positive (must flag) / negative (must stay clean) ---------


class TestUnboundedCompileKey:
    def test_raw_length_in_key_flags(self):
        assert _rules('''
class E:
    def step(self, n):
        key = (n, self.mode)
        fn = self._jit.get(key)
''', "unbounded-compile-key") == ["unbounded-compile-key"]

    def test_bucketed_key_clean(self):
        assert _rules('''
from tnn_tpu.utils.bucketing import pow2_bucket
class E:
    def step(self, n):
        key = (pow2_bucket(n), self.mode)
        fn = self._jit.get(key)
''', "unbounded-compile-key") == []

    def test_min_against_fixed_geometry_clean(self):
        # min() has bounded range as soon as ONE argument is bounded
        assert _rules('''
class E:
    def step(self, n):
        key = (min(n, self.max_batch_size),)
        fn = self._jit[key]
''', "unbounded-compile-key") == []

    # the step_build split: engines key their jit cache on step.key where
    # step came from a packer that buckets internally — bounded only when
    # the packer is a configured bucket_helper
    PACKED = '''
from tnn_tpu.serving import step_build
class E:
    def step(self, rows):
        step = step_build.pack_mixed(rows, b=self.b, nb=self.nb)
        fn = self._jit.get(step.key)
'''

    def test_packed_step_key_clean_with_helper(self):
        vios = lint_source(
            self.PACKED, select=["unbounded-compile-key"],
            options={"unbounded-compile-key":
                     {"bucket_helpers": ["pow2_bucket", "pack_mixed"]}})
        assert vios == []

    def test_attr_of_unbounded_local_still_flags(self):
        # without the helper blessing, step is opaque and step.key raw
        assert _rules(self.PACKED, "unbounded-compile-key") == \
            ["unbounded-compile-key"]


class TestUseAfterDonate:
    BUILDER = '''
import jax
class E:
    def _step_fn(self):
        def fn(pages_k, pages_v):
            return pages_k, pages_v
        return jax.jit(fn, donate_argnums=(0, 1))

    def step(self):
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = self._step_fn()
        pk, pv = fn(self.pool.pages_k, self.pool.pages_v)
'''

    def test_read_after_donation_flags(self):
        src = self.BUILDER + '''
        shape = self.pool.pages_k.shape
        self.pool.update_pages(pk, pv)
'''
        assert _rules(src, "use-after-donate") == ["use-after-donate"]

    def test_read_after_readoption_clean(self):
        src = self.BUILDER + '''
        self.pool.update_pages(pk, pv)
        shape = self.pool.pages_k.shape
'''
        assert _rules(src, "use-after-donate") == []

    # quantized pools: int8 pages travel with separate scale sidecars and
    # BOTH are donated — re-adopting only the pages leaves the scales dead
    SIDECAR_BUILDER = '''
import jax
class E:
    def _step_fn(self):
        def fn(pages_k, pages_v, scales_k, scales_v):
            return pages_k, pages_v, scales_k, scales_v
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3))

    def step(self):
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = self._step_fn()
        pk, pv, sk, sv = fn(self.pool.pages_k, self.pool.pages_v,
                            self.pool.scales_k, self.pool.scales_v)
'''

    def test_dropped_scale_sidecar_flags(self):
        src = self.SIDECAR_BUILDER + '''
        self.pool.update_pages(pk, pv)
'''
        # one finding per dropped sidecar (scales_k AND scales_v)
        assert _rules(src, "use-after-donate") == [
            "use-after-donate", "use-after-donate"]

    def test_full_sidecar_readoption_clean(self):
        src = self.SIDECAR_BUILDER + '''
        self.pool.update_pages(pk, pv, sk, sv)
        shape = self.pool.pages_k.shape
'''
        assert _rules(src, "use-after-donate") == []

    # tensor-parallel builders: no direct jax.jit — the builder returns
    # self._jit_step(fn, donate_argnums=D), which compiles a plain jit at
    # tp=1 and a per-shard shard_map at tp>1. Donation happens on every
    # shard; the rule must keep tracking it through the wrapper.
    WRAPPED_BUILDER = '''
class E:
    def _step_fn(self):
        def fn(params, pages_k, pages_v):
            return pages_k, pages_v
        return self._jit_step(fn, donate_argnums=(1, 2))

    def step(self):
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = self._step_fn()
        pk, pv = fn(self.params, self.pool.pages_k, self.pool.pages_v)
'''

    def test_wrapped_builder_read_after_donation_flags(self):
        src = self.WRAPPED_BUILDER + '''
        shape = self.pool.pages_k.shape
        self.pool.update_pages(pk, pv)
'''
        assert _rules(src, "use-after-donate") == ["use-after-donate"]

    def test_wrapped_builder_readoption_clean(self):
        src = self.WRAPPED_BUILDER + '''
        self.pool.update_pages(pk, pv)
        shape = self.pool.pages_k.shape
'''
        assert _rules(src, "use-after-donate") == []

    # a builder that routes through a mesh context's jit_step directly —
    # same wrapper name, with the routing keywords it took until PR 48
    # (since then a context reads what an argument is off the body's
    # parameter names). Donation happens on every context-mesh shard;
    # further keywords must not confuse the rule's donated-position
    # extraction.
    SP_BUILDER = '''
class E:
    def _step_fn(self):
        def fn(params, pages_k, pages_v, toks, offsets, tables):
            return pages_k, pages_v
        return self._sp.jit_step(fn, donate_argnums=(1, 2), n_outs=2,
                                 tables_argnum=5)

    def step(self):
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = self._step_fn()
        pk, pv = fn(self.params, self.pool.pages_k, self.pool.pages_v,
                    toks, offsets, tables)
'''

    def test_sp_builder_read_after_donation_flags(self):
        src = self.SP_BUILDER + '''
        shape = self.pool.pages_k.shape
        self.pool.update_pages(pk, pv)
'''
        assert _rules(src, "use-after-donate") == ["use-after-donate"]

    def test_sp_builder_readoption_clean(self):
        src = self.SP_BUILDER + '''
        self.pool.update_pages(pk, pv)
        shape = self.pool.pages_k.shape
'''
        assert _rules(src, "use-after-donate") == []

    # the pool's device arrays as ONE donated value (PR 48): a builder
    # donates ``cache`` alone, and a call site takes it back through the
    # pool's setter in the statement that makes the call — a tuple target
    # between the program's small results
    CACHE_BUILDER = '''
class E:
    def _cow_copy_fn(self):
        def fn(cache, src, dst):
            return (), cache, ()
        return self._jit_step("tnn_kv_cow", fn, donate_argnums=(0,))

    def step(self):
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = self._cow_copy_fn()
'''

    def test_one_cache_read_after_donation_flags(self):
        src = self.CACHE_BUILDER + '''
        _, cache, _ = fn(self.pool.cache, src, dst)
        shape = self.pool.cache[0].shape
        self.pool.cache = cache
'''
        assert _rules(src, "use-after-donate") == ["use-after-donate"]

    def test_one_cache_taken_back_in_the_call_statement_clean(self):
        src = self.CACHE_BUILDER + '''
        _, self.pool.cache, _ = fn(self.pool.cache, src, dst)
        shape = self.pool.cache[0].shape
'''
        assert _rules(src, "use-after-donate") == []


class TestHostSyncInStepPath:
    def test_int_on_device_value_flags(self):
        assert _rules('''
class InferenceEngine:
    def step(self):
        fn = self._jit[("d", 4)]
        tok = fn(self.params)
        return int(tok)
''', "host-sync-in-step-path") == ["host-sync-in-step-path"]

    def test_branch_on_device_value_flags(self):
        assert _rules('''
class InferenceEngine:
    def step(self):
        out = self._paged_decode_fn(self.params)
        if out:
            return 1
''', "host-sync-in-step-path") == ["host-sync-in-step-path"]

    def test_batched_device_get_clean(self):
        assert _rules('''
import jax
class InferenceEngine:
    def step(self):
        fn = self._jit[("d", 4)]
        tok = fn(self.params)
        tok = jax.device_get(tok)
        return int(tok)
''', "host-sync-in-step-path") == []

    def test_off_step_path_clean(self):
        # same sync pattern outside the configured roots: not a finding
        assert _rules('''
class Offline:
    def generate(self):
        tok = self._paged_decode_fn(self.params)
        return int(tok)
''', "host-sync-in-step-path") == []


class TestFetchOutsideCommit:
    def test_fetch_in_step_helper_flags(self):
        # a second device_get hidden in a build/commit helper: a stealth
        # pipeline barrier — the exact thing the overlapped loop forbids
        assert _rules('''
import jax
class InferenceEngine:
    def step(self):
        self._commit_rec()

    def _commit_rec(self):
        return int(jax.device_get(self._dev)[0])
''', "fetch-outside-commit") == ["fetch-outside-commit"]

    def test_fetch_inside_commit_helper_clean(self):
        assert _rules('''
import jax
class InferenceEngine:
    def step(self):
        out = self._fetch_bundle([self._dev])

    def _fetch_bundle(self, devs):
        return jax.device_get(tuple(devs))
''', "fetch-outside-commit") == []

    def test_fetch_off_step_path_clean(self):
        # tools/tests off the configured roots may fetch freely
        assert _rules('''
import jax
class Exporter:
    def snapshot(self):
        return jax.device_get(self._dev)
''', "fetch-outside-commit") == []

    # the sharded step: TPContext.jit_step returns a dispatch closure that
    # runs on EVERY engine step — a device_get hidden in it would barrier
    # all tp shards per step, so closures of reachable functions are on
    # the step path too
    TP_OPTS = {"fetch-outside-commit":
               {"step_roots": ["TPContext.jit_step"],
                "commit_helpers": ["InferenceEngine._fetch_bundle"]}}

    def test_fetch_in_tp_dispatch_closure_flags(self):
        vios = lint_source('''
import jax
class TPContext:
    def jit_step(self, fn):
        jitted = self._compile(fn)
        def dispatch(*args):
            return jax.device_get(jitted(*args))
        return dispatch
''', select=["fetch-outside-commit"], options=self.TP_OPTS)
        assert [v.rule for v in vios] == ["fetch-outside-commit"]

    def test_tp_dispatch_returning_device_refs_clean(self):
        vios = lint_source('''
class TPContext:
    def jit_step(self, fn):
        jitted = self._compile(fn)
        def dispatch(*args):
            return jitted(*args)
        return dispatch
''', select=["fetch-outside-commit"], options=self.TP_OPTS)
        assert vios == []

    # same contract for the sequence-parallel dispatcher: the closure
    # SPContext.jit_step returns stages per-shard tables and launches the
    # context-mesh step — a device_get hidden there (say, peeking at the
    # per-shard merge stats) would barrier all sp shards every step
    SP_OPTS = {"fetch-outside-commit":
               {"step_roots": ["SPContext.jit_step"],
                "commit_helpers": ["InferenceEngine._fetch_bundle"]}}

    def test_fetch_in_sp_dispatch_closure_flags(self):
        vios = lint_source('''
import jax
class SPContext:
    def jit_step(self, fn):
        jitted = self._compile(fn)
        def dispatch(*args):
            out = jitted(*args)
            stats = jax.device_get(out[-1])
            return out
        return dispatch
''', select=["fetch-outside-commit"], options=self.SP_OPTS)
        assert [v.rule for v in vios] == ["fetch-outside-commit"]

    def test_sp_dispatch_returning_device_refs_clean(self):
        vios = lint_source('''
class SPContext:
    def jit_step(self, fn):
        jitted = self._compile(fn)
        def dispatch(*args):
            return jitted(*args)
        return dispatch
''', select=["fetch-outside-commit"], options=self.SP_OPTS)
        assert vios == []


class TestPrngKeyReuse:
    def test_double_consumption_flags(self):
        assert _rules('''
def sample(key):
    a = draw(key)
    b = draw(key)
''', "prng-key-reuse") == ["prng-key-reuse"]

    def test_split_between_uses_clean(self):
        assert _rules('''
import jax
def sample(key):
    k1, k2 = jax.random.split(key)
    a = draw(k1)
    b = draw(k2)
''', "prng-key-reuse") == []

    def test_exclusive_branches_clean(self):
        # if/else arms never both execute: one consumption per trace
        assert _rules('''
def sample(key, fast):
    if fast:
        return draw(key)
    else:
        return draw2(key)
''', "prng-key-reuse") == []


class TestCrossThreadEngineAccess:
    def test_unmarked_owner_method_flags(self):
        assert _rules('''
class EngineSupervisor:
    def stats(self):
        return self.engine.metrics.snapshot()
''', "cross-thread-engine-access") == ["cross-thread-engine-access"]

    def test_worker_only_method_clean(self):
        assert _rules('''
from tnn_tpu.serving.ownership import worker_only
class EngineSupervisor:
    @worker_only
    def _tick(self):
        return self.engine.metrics.snapshot()
''', "cross-thread-engine-access") == []

    def test_reach_through_flags(self):
        # any class reaching THROUGH an engine reference is a violation
        assert _rules('''
class Server:
    def health(self):
        return self.sup.engine.scheduler.queue_depth
''', "cross-thread-engine-access") == ["cross-thread-engine-access"]

    def test_passing_engine_reference_clean(self):
        # handing the reference around is fine; dereferencing it is not
        assert _rules('''
class EngineSupervisor:
    def attach(self, sink):
        sink.register(self.engine)
''', "cross-thread-engine-access") == []


class TestUnpairedPoolMutation:
    def test_unchecked_mutation_flags(self):
        assert _rules('''
class PagedKVPool:
    def alloc(self, n):
        block = self._free.pop()
        return block
''', "unpaired-pool-mutation") == ["unpaired-pool-mutation"]

    def test_checked_mutation_clean(self):
        assert _rules('''
class PagedKVPool:
    def alloc(self, n):
        block = self._free.pop()
        self._debug_check()
        return block

    def _debug_check(self):
        if self.debug:
            self.check_invariants()
''', "unpaired-pool-mutation") == []


class TestUnboundedRetry:
    def test_unbounded_retry_loop_flags(self):
        assert _rules('''
class Router:
    def dispatch(self, rec):
        while True:
            try:
                return self._call(lambda: self.sup.submit(rec))
            except ConnectionError:
                continue
''', "unbounded-retry") == ["unbounded-retry"]

    def test_budget_in_condition_clean(self):
        assert _rules('''
class Router:
    def dispatch(self, rec):
        attempt = 0
        while attempt <= self.max_retries:
            attempt += 1
            try:
                return self._call(lambda: self.sup.submit(rec))
            except ConnectionError:
                continue
''', "unbounded-retry") == []

    def test_for_loop_retry_is_inherently_bounded(self):
        # the engine's one-shot decode retry idiom: never flagged
        assert _rules('''
class Engine:
    def decode(self):
        for attempt in (0, 1):
            try:
                return self.engine_step()
            except RuntimeError:
                continue
''', "unbounded-retry") == []

    def test_poll_loop_without_engine_call_clean(self):
        # deadline-bounded queue polls are not retry-around-replica loops
        assert _rules('''
def gather(q, want, deadline):
    got = []
    while len(got) < want:
        try:
            got.append(q.get(timeout=0.5))
        except TimeoutError:
            continue
    return got
''', "unbounded-retry") == []

    def test_unbudgeted_hedge_loop_flags(self):
        # hedge amplification bomb: fire duplicates until something lands
        assert _rules('''
class Router:
    def hedge_all(self, rec):
        while True:
            try:
                return self.fire_hedge(rec)
            except ConnectionError:
                continue
''', "unbounded-retry") == ["unbounded-retry"]

    def test_hedge_budget_in_condition_clean(self):
        assert _rules('''
class Router:
    def hedge_all(self, rec, open_):
        pending = 0
        while pending < self.hedge_budget * open_:
            pending += 1
            try:
                self.fire_hedge(rec)
            except ConnectionError:
                continue
''', "unbounded-retry") == []

    def test_hedge_deadline_in_condition_clean(self):
        # a wall deadline bounds the loop as well as a count budget does
        assert _rules('''
import time
class Router:
    def hedge_until(self, rec, deadline):
        while time.monotonic() < deadline:
            try:
                self.fire_hedge(rec)
            except ConnectionError:
                continue
''', "unbounded-retry") == []

    def test_unbudgeted_scale_up_retry_flags(self):
        # replica-churn bomb: retry a failed join forever against a sick
        # control plane
        assert _rules('''
class Scaler:
    def grow(self):
        while True:
            try:
                return self.router.add_replica(self.factory)
            except ConnectionError:
                continue
''', "unbounded-retry") == ["unbounded-retry"]

    def test_join_retries_budget_clean(self):
        assert _rules('''
class Scaler:
    def grow(self):
        attempts = 0
        while attempts <= self.join_retries:
            attempts += 1
            try:
                return self.router.add_replica(self.factory)
            except ConnectionError:
                continue
''', "unbounded-retry") == []

    def test_hysteresis_bound_counts_as_budget(self):
        # a scaling control loop is bounded by its stability guards, not
        # an attempt counter — hysteresis/cooldown names satisfy the rule
        assert _rules('''
class Scaler:
    def wait_low(self, now):
        while (now - self.low_since) < self.hysteresis_s:
            try:
                now = self.scale_probe()
            except ConnectionError:
                continue
''', "unbounded-retry") == []

    def test_cooldown_bound_counts_as_budget(self):
        assert _rules('''
class Scaler:
    def settle(self, t):
        while (t - self.last_action_t) < self.cooldown_s:
            try:
                t = self.scale_probe()
            except ConnectionError:
                continue
''', "unbounded-retry") == []


class TestTierAdoptUnverified:
    def test_raw_tier_readmit_flags(self):
        assert _rules('''
class Engine:
    def readmit(self, key):
        return self.kv_tier.readmit(key)
''', "tier-adopt-unverified") == ["tier-adopt-unverified"]

    def test_raw_tier_get_flags(self):
        # pulling the raw entry skips the digest check just as surely
        assert _rules('''
class Engine:
    def peek(self, key):
        return self.host_tier.get(key)
''', "tier-adopt-unverified") == ["tier-adopt-unverified"]

    def test_tier_adopt_flags(self):
        assert _rules('''
def splice(tier, key, blk):
    tier.adopt(key, blk)
''', "tier-adopt-unverified") == ["tier-adopt-unverified"]

    def test_verify_readmit_clean(self):
        # the one sanctioned door: digest recomputed, mismatch -> miss
        assert _rules('''
class Engine:
    def readmit(self, key):
        return self.kv_tier.verify_readmit(key)
''', "tier-adopt-unverified") == []

    def test_prefix_cache_adopt_clean(self):
        # device-side index adoption: the receiver is not a tier
        assert _rules('''
class Engine:
    def index(self, key, blk):
        self.prefix_cache.adopt(key, blk)
''', "tier-adopt-unverified") == []

    def test_tier_demote_and_maintenance_clean(self):
        # admission INTO the tier (where the digest is computed) and the
        # stats/maintenance surface are not adoption
        assert _rules('''
class Engine:
    def housekeeping(self, key, leaves):
        self.kv_tier.demote(key, leaves)
        self.kv_tier.clear()
        return self.kv_tier.stats()
''', "tier-adopt-unverified") == []

    # -- cross-replica wire adoption: adopt_blocks on ANY receiver ------------

    def test_wire_adopt_without_verification_flags(self):
        # writing wire bytes into device pages with no digest check in
        # the enclosing function — the disaggregation handoff hole
        assert _rules('''
class Engine:
    def adopt_prefix(self, exports):
        for key, leaves, digest in exports:
            blk = self.pool.alloc(1)
            self.pool.adopt_blocks([(blk[0], leaves[0], leaves[1])],
                                   fn, put)
''', "tier-adopt-unverified") == ["tier-adopt-unverified"]

    def test_wire_adopt_with_tier_digest_clean(self):
        assert _rules('''
class Engine:
    def adopt_prefix(self, exports):
        for key, leaves, digest in exports:
            if tier_digest(key, leaves) != digest:
                break
            blk = self.pool.alloc(1)
            self.pool.adopt_blocks([(blk[0], leaves[0], leaves[1])],
                                   fn, put)
''', "tier-adopt-unverified") == []

    def test_wire_adopt_with_verify_readmit_clean(self):
        # tier re-admission path: verify_readmit IS the digest check
        assert _rules('''
class Engine:
    def readmit(self, key):
        leaves = self.kv_tier.verify_readmit(key)
        if leaves is not None:
            self.pool.adopt_blocks([(3, leaves[0], leaves[1])], fn, put)
''', "tier-adopt-unverified") == []

    def test_wire_adopt_helper_indirection_still_flags(self):
        # the check must be visible AT the adoption site: a verification
        # call in a DIFFERENT function does not sanctify this one
        assert _rules('''
def checked(key, leaves, digest):
    return tier_digest(key, leaves) == digest

class Engine:
    def adopt_prefix(self, exports):
        for key, leaves, digest in exports:
            if not checked(key, leaves, digest):
                break
            self.pool.adopt_blocks([(3, leaves[0], leaves[1])], fn, put)
''', "tier-adopt-unverified") == ["tier-adopt-unverified"]


class TestUnregisteredMetricKey:
    REGISTRY = '''
EXPOSITION = {
    "serve.ttft_s": ("tnn_serve_ttft_seconds", "histogram",
                     "Time to first token", "ttft_ms_p50"),
}
'''

    def test_unregistered_tick_flags(self):
        assert _rules(self.REGISTRY + '''
class M:
    def observe(self, s):
        self._tick("serve.ghost_s", s)
''', "unregistered-metric-key") == ["unregistered-metric-key"]

    def test_registered_tick_clean(self):
        assert _rules(self.REGISTRY + '''
class M:
    def observe(self, s):
        self._tick("serve.ttft_s", s)
''', "unregistered-metric-key") == []

    def test_stale_summary_key_flags(self):
        # the registry names a summary field that summary() no longer has
        assert _rules(self.REGISTRY + '''
class M:
    def summary(self):
        return {"renamed_ttft_p50": 1.0}
''', "unregistered-metric-key") == ["unregistered-metric-key"]

    def test_live_summary_key_clean(self):
        assert _rules(self.REGISTRY + '''
class M:
    def summary(self):
        return {"ttft_ms_p50": 1.0}
''', "unregistered-metric-key") == []

    def test_module_without_registry_ignored(self):
        # engines/supervisors tick through observe_*; only the module
        # owning the registry dict is cross-checked
        assert _rules('''
class Engine:
    def step(self):
        self.metrics._tick("serve.anything", 1.0)
''', "unregistered-metric-key") == []


# -- framework machinery ------------------------------------------------------


POS = '''
class E:
    def step(self, n):
        key = (n,)  {sup}
        fn = self._jit.get(key)
'''


class TestSuppressions:
    def test_justified_suppression_drops_finding(self):
        src = POS.format(
            sup="# tnnlint: disable=unbounded-compile-key -- n is clamped "
                "by the caller")
        assert lint_source(src) == []

    def test_preceding_comment_line_covers_next_line(self):
        src = ('class E:\n'
               '    def step(self, n):\n'
               '        # tnnlint: disable=unbounded-compile-key -- clamped\n'
               '        key = (n,)\n'
               '        fn = self._jit.get(key)\n')
        assert lint_source(src) == []

    def test_bare_suppression_is_itself_a_violation(self):
        src = POS.format(sup="# tnnlint: disable=unbounded-compile-key")
        rules = [v.rule for v in lint_source(src)]
        assert rules == [BARE_SUPPRESSION]

    def test_bare_suppression_cannot_be_suppressed(self):
        src = "x = 1  # tnnlint: disable=bare-suppression -- nice try\n"
        assert [v.rule for v in lint_source(src)] == [BARE_SUPPRESSION]

    def test_unrelated_rule_suppression_does_not_mask(self):
        src = POS.format(sup="# tnnlint: disable=prng-key-reuse -- wrong one")
        assert [v.rule for v in lint_source(src)] == ["unbounded-compile-key"]


class TestDriver:
    def test_all_ten_rules_registered(self):
        assert set(rule_registry()) == {
            "unbounded-compile-key", "use-after-donate",
            "host-sync-in-step-path", "fetch-outside-commit",
            "prng-key-reuse", "cross-thread-engine-access",
            "unpaired-pool-mutation", "unbounded-retry",
            "unregistered-metric-key", "tier-adopt-unverified"}

    def test_unknown_rule_name_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            lint_source("x = 1", select=["no-such-rule"])

    def test_syntax_error_reported_not_raised(self):
        vs = lint_source("def f(:\n")
        assert [v.rule for v in vs] == ["parse-error"]


class TestBaseline:
    def _findings(self):
        return lint_source(POS.format(sup=""), path="fake.py")

    def test_round_trip(self, tmp_path):
        vs = self._findings()
        assert vs
        bl = tmp_path / "baseline.json"
        write_baseline(bl, vs)
        fresh, stale = compare(vs, read_baseline(bl))
        assert fresh == [] and stale == []

    def test_new_finding_is_fresh(self, tmp_path):
        bl = tmp_path / "baseline.json"
        write_baseline(bl, [])
        fresh, stale = compare(self._findings(), read_baseline(bl))
        assert [v.rule for v in fresh] == ["unbounded-compile-key"]
        assert stale == []

    def test_fixed_finding_goes_stale(self, tmp_path):
        bl = tmp_path / "baseline.json"
        write_baseline(bl, self._findings())
        fresh, stale = compare([], read_baseline(bl))
        assert fresh == [] and len(stale) == 1

    def test_fingerprint_survives_line_shift(self):
        a = lint_source(POS.format(sup=""), path="fake.py")[0]
        b = lint_source("\n\n" + POS.format(sup=""), path="fake.py")[0]
        assert a.line != b.line
        assert a.fingerprint() == b.fingerprint()


class TestCli:
    def test_clean_file_exits_zero(self, tmp_path, capsys):
        f = tmp_path / "ok.py"
        f.write_text("x = 1\n")
        assert main([str(f), "--no-baseline"]) == 0
        assert "clean" in capsys.readouterr().out

    def test_violation_exits_one(self, tmp_path, capsys):
        f = tmp_path / "bad.py"
        f.write_text(POS.format(sup=""))
        assert main([str(f), "--no-baseline"]) == 1
        assert "unbounded-compile-key" in capsys.readouterr().out

    def test_write_then_check_baseline(self, tmp_path, capsys):
        f = tmp_path / "bad.py"
        f.write_text(POS.format(sup=""))
        bl = tmp_path / "bl.json"
        assert main([str(f), "--baseline", str(bl), "--write-baseline"]) == 0
        capsys.readouterr()
        # baselined: same findings no longer fail the run
        assert main([str(f), "--baseline", str(bl)]) == 0

    def test_stale_baseline_entry_exits_one(self, tmp_path, capsys):
        f = tmp_path / "bad.py"
        f.write_text(POS.format(sup=""))
        bl = tmp_path / "bl.json"
        assert main([str(f), "--baseline", str(bl), "--write-baseline"]) == 0
        f.write_text("x = 1\n")  # fixed: baseline entry is now stale
        capsys.readouterr()
        assert main([str(f), "--baseline", str(bl)]) == 1
        assert "stale" in capsys.readouterr().out

    def test_unknown_rule_exits_two(self, tmp_path, capsys):
        f = tmp_path / "ok.py"
        f.write_text("x = 1\n")
        assert main([str(f), "--select", "bogus", "--no-baseline"]) == 2


# -- the tier-1 gate ----------------------------------------------------------


class TestRepoGate:
    def test_tnn_tpu_lints_clean(self):
        """The enforced contract: zero findings over the whole package with
        the committed pyproject config. New violations fail here until fixed
        or suppressed with an inline justification."""
        cfg = load_config(REPO)
        vs = lint_paths([str(REPO / p) for p in cfg["paths"]],
                        options=cfg["rules"], ignore=cfg["ignore"],
                        exclude=cfg["exclude"])
        assert vs == [], "\n" + "\n".join(v.render() for v in vs)

    def test_committed_baseline_is_empty(self):
        baseline = read_baseline(REPO / "tools" / "tnnlint" / "baseline.json")
        assert baseline == {}, (
            "the baseline must stay empty — fix new findings or add an "
            "inline justified suppression instead of baselining them")

    def test_cli_default_invocation_clean(self, monkeypatch, capsys):
        monkeypatch.chdir(REPO)
        assert main([]) == 0
