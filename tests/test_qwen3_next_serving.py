"""Qwen3-Next's layers on the normal serving path (PR 44): Gated DeltaNet
layers whose state is updated IN PLACE at every position, three to one gated
full-attention layer with pages; state slots beside the pages; a softmax
router at top-4 of 16 beside a gated shared expert. Tiny sizes on the CPU (4
layers: linear, linear, linear, full; 64 wide), seeded weights, logits held
against ``chipbench/reference/qwen3_next.py``: the same module the benchmark
compares with, which imports nothing of the program and runs the recurrence
position by position.

Since PR 49 the harness of the SERVING path (chunked prefill, the chains
rolled back at every depth, preemption, admission, the refusals) runs over
BOTH state models (``FAMILIES``): granite-4.0-h-micro's Mamba-2 layers keep
their state in the same slots by the same protocol, so every case counts for
both. What is one model's own (a router, an equation's control) names its
family (``only``); Granite's own are ``tests/test_granite_hybrid_serving.py``."""
import contextlib
import hashlib
import inspect
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import granite_hybrid
from chipbench.reference import qwen3_next as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.nn import attention as attn_lib
from tnn_tpu.nn.moe import ExpertShare
from tnn_tpu.ops.pallas import gdn_step as gdn
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving import engine as engine_lib
from tnn_tpu.serving.engine import refuse_windowed
from tnn_tpu.serving.kv_pool import PagedKVPool
from tnn_tpu.serving.scheduler import RequestState

CFG = spec.load_json("chipbench", "configs",
                     "qwen3-next-ep4-serve.json")["rehearsal"]
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The program in float32 (chunked closed form, slots, pages, sorted experts)
# against the float32 reference at precision "highest" (one position at a
# time, no cache, experts one at a time): what is left is the order of sums.
TOL = 2e-4
SHARE = dict(num_experts=16, top_k=4, hidden=32, shared=1, shared_gated=True)
# The two models that keep a state in the pool's slots. ``tol``: Granite's
# tied head over a table drawn small (reference/granite_hybrid.embed_std)
# gives logits of deviation 0.003, a hundredth of Qwen3-Next's; ``bf16``: how
# far the program in bfloat16 may and must lie from the float32 reference
FAMILIES = {
    "qwen3_next": dict(
        ref=ref, cfg=CFG, name="qwen3_next_tiny", seed=44, tol=TOL,
        state_layers=3, bf16=(1e-3, 1.5)),
    "granite_hybrid": dict(
        ref=granite_hybrid, name="granite4_h_micro_cpu", seed=49, tol=2e-6,
        state_layers=4, bf16=(1e-5, 1.5e-2),
        cfg=spec.load_json("chipbench", "configs",
                           "granite4-h-micro-serve.json")["rehearsal"])}
only = lambda name: pytest.mark.parametrize(    # noqa: E731
    "family", [name], indirect=True)


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]


@pytest.fixture(scope="module")
def sz(family):
    return family["ref"].sizes_of(family["cfg"])


@pytest.fixture(scope="module")
def weights(family, sz):
    p = family["ref"].make_params(sz, family["seed"])
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(family, sz):
    m = models.create(family["name"])               # float32 by default
    family["ref"].check_program(m, sz, family["name"])
    return m


@pytest.fixture(scope="module")
def forward(family, weights, sz):
    return family["ref"].Forward(weights[0], sz, 128)


def engine(model, params, **kw):
    kw = dict(dict(num_blocks=96, block_size=8, max_batch_size=4,
                   chunk_size=16, prefix_cache=False, max_seq_len=192), **kw)
    return InferenceEngine(model, params, **kw)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# -- (1) prefill in chunks, then decode, through slots and pages -----------------

def _served_logits(model, params, ids, n_prompt, chunk, layers, bs=8,
                   slot=2):
    """Chunked prefill (ragged: the last chunk is short) and then decode,
    one sequence in row 1 of a batch of 2 (row 0 is padding: the scratch
    slot), straight through ``apply_paged`` / ``apply_decode_paged`` with
    the state beside the pages: logits at every position, and the state."""
    pool = PagedKVPool(model.cache_layers, model.num_kv_heads, model.head_dim,
                       32, bs, dtype=jnp.float32, state=model.state_group,
                       state_rows=3)
    assert pool.page_shape[0] == 1 and pool.slots.layers == layers
    # the slot holds a last tenant's garbage: a row at position 0 ignores it
    state = jax.tree_util.tree_map(lambda x: x + 7.0, pool.state)
    table = np.zeros((2, 17), np.int32)
    table[1, :16], table[1, -1] = np.arange(1, 17), slot
    pk, pv = pool.pages_k, pool.pages_v
    out, at = [], 0
    while at < n_prompt:
        n = min(chunk, n_prompt - at)
        toks = np.zeros((2, chunk), np.int32)
        toks[1, :n] = ids[at:at + n]
        lg, pk, pv, state = model.apply_paged(
            params, jnp.asarray(toks), pk, pv, jnp.asarray(table),
            jnp.asarray([0, at]), jnp.asarray([0, n]), state=state)
        out.append(np.asarray(lg[1, :n]))
        at += n
    for t in range(n_prompt, len(ids)):
        lg, pk, pv, state = model.apply_decode_paged(
            params, jnp.asarray([0, ids[t]]), pk, pv, jnp.asarray(table),
            jnp.asarray([0, t]), state=state)
        out.append(np.asarray(lg[1:2]))
    return np.concatenate(out), state


@pytest.mark.parametrize("n_prompt,chunk", [
    (37, 16), (32, 16), (33, 32), (64, 32), (9, 8), (16, 16), (5, 32)])
def test_chunked_prefill_then_decode_match_the_reference(
        family, model, weights, forward, n_prompt, chunk):
    """Prompts that are and are not whole chunks, chunks of 8, 16 and 32
    (one sub-chunk of the closed form, and two): every logit of the prefill
    and of the decode steps behind it."""
    ids = _ids(n_prompt, n_prompt + 20)
    want = forward.rows(list(ids), np.arange(len(ids)))
    got, state = _served_logits(model, weights[1], ids, n_prompt, chunk,
                                family["state_layers"])
    assert np.abs(got - want).max() < family["tol"]
    # the other slots were never touched; the snapshots of slot 2 hold the
    # states of the multiples of 16 the row STARTED a step at
    assert np.all(np.asarray(state["rec"][:, 1]) == 7.0)
    assert np.all(np.asarray(state["rec_snap"][:, 1:3]) == 7.0)


def test_the_plain_forward_the_cached_one_and_bf16(family, model, weights,
                                                   forward):
    tol = family["tol"]
    ids = _ids(3, 60)
    want = forward.rows(list(ids), np.arange(60))
    plain, _ = model.apply({"params": weights[1], "state": {}},
                           jnp.asarray(ids)[None])
    assert np.abs(np.asarray(plain[0]) - want).max() < tol
    caches = model.init_cache(1, 64)
    lg, caches = model.apply_cached(weights[1], jnp.asarray(ids[None, :41]),
                                    caches, 0)
    outs = [lg]
    for t in range(41, 60):
        lg, caches = model.apply_cached(
            weights[1], jnp.asarray(ids[None, t:t + 1]), caches, t)
        outs.append(lg)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1)[0]) - want).max() < tol
    bf16 = models.create(family["name"], policy=DTypePolicy(
        io="bfloat16", param="bfloat16", compute="bfloat16"))
    low, _ = bf16.apply({"params": weights[0], "state": {}},
                        jnp.asarray(ids)[None])
    least, most = family["bf16"]
    assert least < np.abs(np.asarray(low[0], np.float32) - want).max() < most


# -- (2) the chunked form against the position-by-position form ------------------

@pytest.mark.parametrize("width,sub", [(16, 16), (32, 16), (32, 32), (7, 16),
                                       (48, 16)])
def test_the_closed_form_is_the_recurrence(width, sub):
    rng = np.random.default_rng(width)
    b, h, dk, dv = 3, 4, 16, 16

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(rng.normal(size=(b, width, h, dk))) * dk ** -0.5)
    k = unit(rng.normal(size=(b, width, h, dk)))
    v = rng.normal(size=(b, width, h, dv))
    g = -0.3 * np.abs(rng.normal(size=(b, width, h)))
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, width, h))))
    s0 = rng.normal(size=(b, h, dk, dv))
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, s0)]
    s, outs = args[5], []
    for t in range(width):
        o, s = gdn.step_math(s, *(x[:, t] for x in args[:5]))
        outs.append(o)
    with jax.default_matmul_precision("highest"):
        got, s1 = gdn.gdn_chunk(*args, sub=sub) if width % min(sub, width) \
            == 0 else attn_lib.GatedDeltaNet(2, 4, 16, 16)._scan(
                None, tuple(args[:5]), args[5])
    assert np.abs(np.asarray(got) - np.asarray(jnp.stack(outs, 1))).max() \
        < 2e-6
    assert np.abs(np.asarray(s1) - np.asarray(s)).max() < 2e-6


@pytest.mark.kernel
def test_the_step_kernel_is_the_step_and_keeps_what_it_read():
    """``tnn_gdn_step`` (interpreted) against the ``jax.numpy`` step: the
    output, the live state written once, and the state a row READ copied into
    its snapshot slot where it has one; a row without keeps nothing."""
    rng = np.random.default_rng(0)
    b, h, dk, dv, layers, slots = 3, 8, 16, 16, 2, 5
    q, k = (jnp.asarray(rng.normal(size=(b, h, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, h, dv)), jnp.float32)
    g = -jnp.abs(jnp.asarray(rng.normal(size=(b, h)), jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(b, h)), jnp.float32))
    rec = jnp.asarray(rng.normal(size=(layers, slots, h, dk, dv)),
                      jnp.float32)
    snap = jnp.zeros((layers, 2 * slots, h, dk, dv), jnp.float32)
    at, keep = jnp.asarray([1, 3, 0]), jnp.asarray([2, 0, 0])
    want = gdn.gdn_step(q, k, v, g, beta, rec, snap, at, keep, layer=1,
                        backend="xla")
    got = gdn.gdn_step(q, k, v, g, beta, rec, snap, at, keep, layer=1,
                       backend="pallas", interpret=True)
    assert np.abs(np.asarray(got[0]) - np.asarray(want[0])).max() < 1e-6
    assert np.abs(np.asarray(got[1])[:, 1:] - np.asarray(want[1])[:, 1:]
                  ).max() < 1e-6
    assert np.array_equal(np.asarray(got[2][1, 2]), np.asarray(rec[1, 1]))
    assert not np.asarray(got[2][:, 3:]).any() \
        and not np.asarray(got[2][0]).any()
    assert np.array_equal(np.asarray(got[1][0]), np.asarray(rec[0]))


# -- (3) the four shares add up to the uncut layer -------------------------------

@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_with_router_and_shared_expert_once_are_the_layer(chips):
    """Sixteen experts on ``chips`` chips: the routed parts of the shares and
    the gated shared expert ONCE add up to the layer with every expert held;
    what a share leaves out is exactly what the others add."""
    whole = ExpertShare(held=range(16), policy=F32, **SHARE)
    p = whole.init(jax.random.PRNGKey(3), (1, 64))["params"]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    full, _ = whole.apply({"params": p, "state": {}}, x)
    total = whole.shared_out(p, x).astype(jnp.float32)
    n = 16 // chips
    for c in range(chips):
        held = range(c * n, (c + 1) * n)
        share = ExpertShare(held=held, policy=F32, **SHARE)
        part = dict(p, **{k: p[k][c * n:(c + 1) * n]
                          for k in ("gate", "up", "down")})
        y, counts = share.routed(part, x)
        total = total + y
        assert int(counts.sum()) > 0
    assert np.abs(np.asarray(total) - np.asarray(full)).max() < 1e-5
    # the gate is the token's own: ungated the shared expert reads otherwise
    plain = ExpertShare(held=range(16), policy=F32,
                        **dict(SHARE, shared_gated=False))
    assert np.abs(np.asarray(plain.shared_out(p, x))
                  - np.asarray(whole.shared_out(p, x))).max() > 1e-3


@only("qwen3_next")
@pytest.mark.parametrize("seed", [0, 1])
def test_top4_of_a_softmax_renormalised_agree_with_the_reference(
        sz, weights, seed):
    share = ExpertShare(held=range(8), policy=F32, **SHARE)
    p = weights[1]["h1"]["moe"]
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 64)),
                    jnp.float32)
    ids, w = share.route(p, x)
    dense = np.zeros((24, 16), np.float32)
    np.put_along_axis(dense, np.asarray(ids), np.asarray(w), axis=1)
    assert np.abs(dense - np.asarray(ref.route(p, x, sz))).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


# -- (4) a chain rolled back at every depth ---------------------------------------

PROMPTS = (37, 16, 50)


def _sync_tokens(model, params, new=60, skip=()):
    eng = engine(model, params)
    rids = [eng.submit(_ids(10 + i, n), new)
            for i, n in enumerate(PROMPTS) if i not in skip]
    out = eng.run_until_complete()
    return [out[r] for r in rids]


@pytest.fixture(scope="module")
def sync(model, weights):
    return _sync_tokens(model, weights[1])


def _drive(eng, at_depth, event, after=20):
    """The overlapped loop by hand: once ``after`` steps have passed and the
    queue behind the step in flight is ``at_depth`` deep, ``event(eng)``
    happens WHILE the chain flies. Returns the chain's depth then."""
    steps, seen = 0, None
    while eng.has_work or eng.in_flight is not None:
        if eng.in_flight is None:
            eng.begin_step()
        while eng.try_speculate():
            pass
        if seen is None and steps >= after \
                and len(eng.in_flight.ahead) >= at_depth:
            seen = len(eng.in_flight.ahead)
            event(eng)
        eng.run_deferred()
        eng.finish_step()
        steps += 1
    eng.check_invariants()
    return seen


@pytest.mark.parametrize("depth", range(1, 13))
def test_a_chain_rolled_back_by_a_cancel_gives_the_synchronous_tokens(
        model, weights, sync, depth, monkeypatch):
    """A row cancelled while ``depth`` steps are queued behind the step in
    flight: every one of them has advanced the surviving rows' states. The
    roll-back puts the snapshot back and pushes the committed tokens behind
    it again; the streams are the synchronous loop's."""
    monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", 1)
    monkeypatch.setattr(engine_lib, "SPECULATE_MAX", depth)
    eng = engine(model, weights[1], overlap=True)
    rids = [eng.submit(_ids(10 + i, n), 60) for i, n in enumerate(PROMPTS)]
    seen = _drive(eng, depth, lambda e: e.cancel(rids[1]))
    assert seen == depth
    assert eng.output_tokens(rids[0]) == sync[0]
    assert eng.output_tokens(rids[2]) == sync[2]
    s = eng.metrics.summary()
    assert s["overlap_rebuilds"] >= 1 and s["state_restores"] == 2
    assert 0 < s["state_replayed_tokens"] <= 2 * (attn_lib.SNAPSHOT_EVERY - 1)
    assert eng.pool.slots.num_free == 4 and eng.pool.num_allocated == 0


@pytest.mark.parametrize("depth", [1, 5, 12])
def test_a_chain_rolled_back_by_an_arrival_gives_the_synchronous_logits(
        family, model, weights, forward, depth, monkeypatch):
    """An arrival the scheduler admits (a free row) rolls the chain back;
    the rows that were decoding replay at most 15 tokens, and the arrival is
    served from a slot's zeros: every row's tokens are the reference's own
    greedy choice (its logits' first), not merely the synchronous loop's."""
    monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", 1)
    monkeypatch.setattr(engine_lib, "SPECULATE_MAX", depth)
    eng = engine(model, weights[1], overlap=True)
    prompts = [_ids(10 + i, n) for i, n in enumerate(PROMPTS)]
    rids = [eng.submit(p, 50) for p in prompts[:2]]
    _drive(eng, depth, lambda e: rids.append(e.submit(prompts[2], 50)))
    s = eng.metrics.summary()
    assert s["state_restores"] == 2 and s["overlap_rebuilds"] >= 1
    for rid, p in zip(rids, prompts):
        out = eng.output_tokens(rid)
        lg = forward.rows(list(p) + out, np.arange(len(p) - 1,
                                                   len(p) + len(out) - 1))
        gap = lg.max(-1) - lg[np.arange(len(out)), out]
        assert len(out) == 50 and gap.max() < family["tol"]


def test_the_depth_of_the_queue_is_held_to_the_snapshot_interval():
    """A decoding row's snapshot at ``c - c % 16`` is overwritten by the step
    from 32 positions on: never inside a chain, because the chain is no
    deeper than the interval. The program and the host count alike."""
    assert engine_lib.SPECULATE_MAX <= attn_lib.SNAPSHOT_EVERY == 16
    slots = np.asarray([0, 1, 1, 1, 3, 3])
    offs = np.asarray([16, 0, 16, 17, 32, 48])
    want = [0, 1, 2, 0, 5, 6]
    assert list(attn_lib.snapshot_slots(slots, offs, np)) == want
    assert list(map(int, attn_lib.snapshot_slots(
        jnp.asarray(slots), jnp.asarray(offs)))) == want


def test_a_prompt_row_in_a_rolled_back_chain_starts_again(
        model, weights, sync, monkeypatch):
    """A row still pushing its prompt passes both snapshots inside a chain:
    rolled back, it is recomputed from its first token."""
    monkeypatch.setattr(engine_lib, "SPECULATE_RAMP", 1)
    eng = engine(model, weights[1], overlap=True, chunk_size=4)
    rids = [eng.submit(_ids(10 + i, n), 60) for i, n in enumerate(PROMPTS)]
    _drive(eng, 3, lambda e: e.cancel(rids[1]), after=2)
    assert eng.output_tokens(rids[0]) == sync[0]
    assert eng.output_tokens(rids[2]) == sync[2]
    assert eng.metrics.summary()["state_replayed_tokens"] > 0


# -- (5) preemption, resume, and a slot's next tenant ------------------------------

def test_a_preempted_row_resumes_and_a_freed_slot_leaks_nothing(
        model, weights, sync):
    eng = engine(model, weights[1], overlap=True)
    rids = [eng.submit(_ids(10 + i, n), 60) for i, n in enumerate(PROMPTS)]
    for _ in range(12):
        eng.step()
    victim = eng.requests[rids[2]]
    slot = victim.state_slot
    eng._preempt(victim)
    assert victim.state is RequestState.QUEUED and victim.state_slot == 0
    assert eng.pool.slots.num_free == 2
    out = eng.run_until_complete()
    eng.check_invariants()
    assert [out[r] for r in rids] == sync
    assert eng.metrics.summary()["preemptions"] == 1
    # the same engine again: every slot has had a tenant, and the new ones
    # read none of it
    again = [eng.submit(_ids(10 + i, n), 60) for i, n in enumerate(PROMPTS)]
    out = eng.run_until_complete()
    assert [out[r] for r in again] == sync and slot in (1, 2, 3, 4)
    assert eng.pool.slots.num_free == 4


def test_admission_counts_a_slot_beside_the_pages(model, weights):
    eng = engine(model, weights[1], max_batch_size=2)
    assert eng.pool.slots.rows == 2
    assert eng.blocks_per_seq == eng.pool.blocks_for(192) + 1
    rids = [eng.submit(_ids(i, 20), 8) for i in range(3)]
    eng.step()
    assert [eng.requests[r].state_slot for r in rids] == [1, 2, 0]
    assert eng.scheduler.queue_depth == 1
    eng.pool.slots._free.append(9)          # a slot nobody may hold
    with pytest.raises(ValueError, match="not a partition"):
        eng.check_invariants()
    eng.pool.slots._free.pop()
    out = eng.run_until_complete()
    assert len(out) == 3 and eng.pool.slots.num_free == 2
    s = eng.metrics.summary()
    assert s["state_slots_occupancy_max"] == 1.0 and s["state_snapshots"] > 0
    names = {f["name"] for f in eng.metrics.prometheus_series()}
    assert {"tnn_serve_state_slots_occupancy_max",
            "tnn_serve_state_snapshots_total",
            "tnn_serve_state_restores_total",
            "tnn_serve_state_replayed_tokens_total"} <= names


# -- (6) the comparison can see the state ------------------------------------------

@only("qwen3_next")
@pytest.mark.parametrize("without", ref.WITHOUT)
def test_the_comparison_sees_each_equation(weights, sz, forward, without):
    ids = _ids(7, 100)
    pos = np.arange(40, 100)
    want = forward.rows(list(ids), pos)
    got = ref.Forward(weights[0], sz, 128, without=(without,)).rows(
        list(ids), pos)
    assert np.abs(got - want).max() > 0.05


@only("qwen3_next")
def test_the_comparison_sees_a_wrong_state(weights, sz, forward):
    """One position's state update left out, or the state kept in bfloat16
    for 512 positions, moves a logit past the tiny limits."""
    limits = CFG["limits"]
    ids = _ids(8, 100)
    pos = np.arange(40, 100)
    want = forward.rows(list(ids), pos)
    skipped = ref.Forward(weights[0], sz, 128, skip_update=30).rows(
        list(ids), pos)
    assert np.abs(skipped - want).max() > limits["gap_max"]
    long = _ids(9, 512)
    pos = np.arange(256, 511)
    exact = ref.Forward(weights[0], sz, 512).rows(list(long), pos)
    low = ref.Forward(weights[0], sz, 512, quant="state_bf16").rows(
        list(long), pos)
    first = exact.argmax(-1)
    gap = exact.max(-1) - exact[np.arange(len(pos)), low.argmax(-1)]
    assert np.abs(low - exact).max() > limits["gap_max"]
    assert gap.mean() > limits["gap_mean"] or (low.argmax(-1) != first).any()


def test_the_engine_serves_the_reference_tokens(model, weights, forward):
    eng = engine(model, weights[1], overlap=True)
    p = _ids(0, 37)
    rid = eng.submit(p, 80)
    out = eng.run_until_complete()[rid]
    eng.check_invariants()
    lg = forward.rows(list(p) + out, np.arange(36, 116))
    assert (lg.argmax(-1) == np.asarray(out)).all()
    s = eng.metrics.summary()
    if model.experts:
        assert 0 < s["experts_hit_share"] <= 1 and s["expert_held_share"] > 0
    assert s["state_restores"] == 0 and s["adopted_step_share"] > 0.5


# -- (7) the refusals ---------------------------------------------------------------

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=True), "prefix sharing"),
    (dict(spec="ngram"), "speculative decoding"),
    (dict(tp=2), "tensor parallelism"),
    (dict(sp=2), "sequence parallelism"),
    (dict(prefix_cache=True, host_tier_bytes=1 << 20), "prefix sharing"),
    (dict(kv_dtype="int8"), "int8 pages")])
def test_the_engine_refuses_what_assumes_pages_alone(model, weights, kw,
                                                     what):
    with pytest.raises(ValueError, match="a state updated in place") as e:
        engine(model, weights[1], **kw)
    assert what in str(e.value) and str(e.value).count(".") <= 1


def test_one_refusal_function_and_the_other_four_read_as_before(family,
                                                                model):
    msg = refuse_windowed(model, host_tier_bytes=1 << 20)
    assert "host KV tier" in msg \
        and f"{family['state_layers']} of its layers" in msg
    assert refuse_windowed(model) is None
    eva = refuse_windowed(models.create("evabyte_tiny"), prefix_cache=True)
    assert "exact window of 32" in eva and "state" not in eva
    lat = refuse_windowed(models.create("longcat_flash_tiny"), spec=True)
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
def test_tnn_serve_says_so_at_start_up_before_any_weights(family, flags,
                                                          what):
    from tnn_tpu.cli import serve

    err = io.StringIO()
    with contextlib.redirect_stderr(err), pytest.raises(SystemExit) as e:
        serve.main(["--model", family["name"], *flags])
    assert e.value.code == 2 and what in err.getvalue()
    assert "random-weight" not in err.getvalue()


# -- (8) the older configurations' programs are the parent's ------------------------

# sha256 of ``lower(...).as_text()`` of the engine's own decode and mixed
# step builder with abstract arguments, made with ``_older_programs`` below.
# PR 44 took them on its PARENT (65e11db) and held them through its change;
# PR 48 took them anew on its own tree, because it changed every program's
# text on purpose (the cache one argument and one result between the small
# ones, the head at a mixed step's last live position): what they hold from
# here on is that a LATER configuration leaves these programs alone. PR 49
# added Qwen3-Next's two, taken on ITS parent (0eff4b3), before the two state
# mixers came to share ``nn.attention._StateMixer``: six configurations,
# twelve programs
PARENT = {
    "gpt2_tiny": {
        "decode":
        "f96f24465697eef2b517389a29d4cdf17be579a1c20dfa7f7ccbd530d614e248",
        "mixed":
        "4282a4184f9e5b55cb884c65a4cd125f8e6dac847e26bd5ead3530c21a8ab46c",
    },
    "evabyte_tiny": {
        "decode":
        "ddb1acfab25479c7ee452d357c9a5fb4597b19692450854e20ff8bb5924d0045",
        "mixed":
        "497828ae733bb1151bfea2104fb4041fa07cdd0db4d624ec4ed7c704c05a2557",
    },
    "mistral_small4_tiny": {
        "decode":
        "3670fff66f14f8629b2c88efc790df9c0c9f3649ec2b510e2c0b85ccb30e4a0d",
        "mixed":
        "da8c639a3aa447899da0326b33e0a09baf2326c7637343a79c39974988c56a48",
    },
    "trinity_large_tiny": {
        "decode":
        "4c7618ca1c01d0f1f98d45c42f154146b80102eb53153e6efd544978bfa7527f",
        "mixed":
        "9873a87816aca3c4444eff1919ec04c6510f6729f24546b83d84d3044e7d779f",
    },
    "longcat_flash_tiny": {
        "decode":
        "0c9cae43e91f03ca3618c0dcb9e02ef78fd118893072f625057e9bcb09afeedd",
        "mixed":
        "eb6d77e465c277155bc4732e4e1157b4d2a0f62d457b4a7087a2dbae35968ee8",
    },
    "qwen3_next_tiny": {
        "decode":
        "a5faacf2f85b3e6c568818cf161fc2415a08d8ddca4ee50fe7d0693e817bd927",
        "mixed":
        "0754e5ea64c3a383674c4d07e9965498b0f6976b1a0a7b1cf4a16ad50d0175ae",
    },
}


def _lowered_programs(name):
    """{kind: (the engine's own builder's program, lowered with abstract
    arguments)} of configuration ``name``: its decode program and its
    16-wide mixed program."""
    m = models.create(name)
    params = m.init(jax.random.PRNGKey(0), (1, 8))["params"]
    bs = 16 if name == "trinity_large_tiny" else 32 if name == \
        "evabyte_tiny" else 8
    eng = InferenceEngine(m, params, num_blocks=64, block_size=bs,
                          max_batch_size=4, chunk_size=16,
                          prefix_cache=name == "gpt2_tiny", max_seq_len=128)
    b, nb = 4, eng.blocks_per_seq

    def spec_of(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    i32, f32 = jnp.int32, jnp.float32
    tail = (spec_of((b,), f32), spec_of((b,), i32), spec_of((b,), f32),
            spec_of((2,), jnp.uint32), spec_of((b,), f32))
    decode, mixed = eng._step_program(None), eng._step_program(16)
    return {
        "decode": (decode, decode.lower(
            eng.params, eng.pool.cache, spec_of((b,), i32),
            spec_of((b,), i32), None, spec_of((b, nb), i32), *tail)),
        "mixed": (mixed, mixed.lower(
            eng.params, eng.pool.cache, spec_of((b, 16), i32),
            spec_of((b,), i32), spec_of((b,), i32), spec_of((b, nb), i32),
            *tail))}


def _older_programs(name):
    """{kind: sha256 of the lowered text} of ``_lowered_programs(name)``."""
    return {kind: hashlib.sha256(low.as_text().encode()).hexdigest()
            for kind, (_, low) in _lowered_programs(name).items()}


@pytest.mark.parametrize("name", ["gpt2_tiny", "evabyte_tiny",
                                  "mistral_small4_tiny",
                                  "trinity_large_tiny",
                                  "longcat_flash_tiny", "qwen3_next_tiny"])
def test_the_older_configurations_programs_hash_as_the_parents(name):
    assert _older_programs(name) == PARENT[name]


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_state_slots_are_one_more_leaf_of_the_one_cache(kind):
    """A model with state slots and one without build their step programs
    from ONE body: the same parameters in the same order, the pool's arrays
    the one donated argument, the state its third leaf (PR 48: the twin
    bodies that differed in one positional argument are gone)."""
    order = ["params", "cache", "toks", "starts", "q_lens", "tables", "t",
             "k", "p", "key", "poison"]
    seen = {}
    for name in ("gpt2_tiny", "qwen3_next_tiny", "granite4_h_micro_cpu"):
        program, low = _lowered_programs(name)[kind]
        assert list(inspect.signature(program).parameters) == order
        args = low.args_info[0]
        cache = jax.tree_util.tree_leaves(args[1])
        assert all(x.donated for x in cache)
        assert not any(x.donated for i, a in enumerate(args) if i != 1
                       for x in jax.tree_util.tree_leaves(a))
        # behind the cache: the same leaves, shape for shape (the step's
        # table is one entry wider where its last entry is the state slot)
        seen[name] = (len(cache), jax.tree_util.tree_structure(args[2:]),
                      [x.shape for x in jax.tree_util.tree_leaves(args[2:])
                       if len(x.shape) < 2])
        assert jax.tree_util.tree_structure(args[1]) == \
            jax.tree_util.tree_structure(
                (0, 0) if name == "gpt2_tiny" else
                (0, 0, dict(conv=0, rec=0, conv_snap=0, rec_snap=0)))
    assert seen["gpt2_tiny"][0] == 2 and seen["qwen3_next_tiny"][0] == 6 \
        == seen["granite4_h_micro_cpu"][0]
    assert seen["gpt2_tiny"][1:] == seen["qwen3_next_tiny"][1:] \
        == seen["granite4_h_micro_cpu"][1:]


# -- the served model, as published ---------------------------------------------------

def test_the_published_sizes_of_the_served_model():
    """``qwen3_next_ep4`` as the cell runs it: every width the source's."""
    m = models.create("qwen3_next_ep4")
    assert (m.num_layers, m.cache_layers, m.d_model, m.num_heads,
            m.num_kv_heads, m.head_dim, m.vocab_size) == (
                8, 2, 2048, 16, 2, 256, 37984)
    assert m.gated["layer_types"] == (["linear_attention"] * 3
                                      + ["full_attention"]) * 2
    assert m.state_group == dict(layers=6, conv=(48, 512),
                                 rec=(32, 128, 128))
    assert m.page_groups is None and m.norm_unit_offset
    mix = m.blocks[0].attn
    assert (mix.key_heads, mix.value_heads, mix.key_dim, mix.value_dim,
            mix.conv, mix.channels) == (16, 32, 128, 128, 4, 8192)
    full = m.blocks[3].attn
    assert (full.head_dim, full.rotary_dim, full.rope_theta, full.window,
            full.norm_unit_offset) == (256, 64, 1e7, None, True)
    moe = m.blocks[7].moe
    assert (moe.num_experts, moe.top_k, moe.hidden, len(moe.held),
            moe.shared, moe.shared_gated, moe.score) == (
                512, 10, 512, 128, 1, True, "softmax")
    shapes = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), (1, 8))["params"])
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 3.667e9) < 0.001e9       # 7.33 GB of bf16
    assert shapes["h0"]["attn"]["qkvz_kernel"].shape == (2048, 12288)
    assert shapes["h0"]["attn"]["A_log"].dtype == jnp.float32
    assert shapes["h3"]["attn"]["qkvg_kernel"].shape == (2048, 9216)
    assert shapes["h3"]["moe"]["shared_router"].shape == (2048, 1)
    from tnn_tpu.models.llama import Llama

    with pytest.raises(ValueError, match="named by layer_types"):
        Llama(vocab_size=8, num_layers=1, d_model=8, num_heads=1,
              linear=dict(key_heads=1, value_heads=1, key_dim=8, value_dim=8))
