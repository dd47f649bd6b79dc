"""granite-4.0-h-micro's layers on the normal serving path (PR 49): Mamba-2
mixers whose state-space state is updated IN PLACE at every position, beside
plain grouped-query attention WITHOUT positions at a published softmax
scale; a gated feed-forward in every layer; the four multipliers. Tiny sizes
on the CPU (5 layers: mamba, mamba, attention, mamba, mamba; 64 wide), seeded
weights, logits held against ``chipbench/reference/granite_hybrid.py``: the
same module the benchmark compares with, which imports nothing of the
program and runs the recurrence position by position.

The serving path itself (chunked prefill then decode through slots and
pages, chains rolled back at depths 1 to 12, preemption, admission, the
refusals) is ``tests/test_qwen3_next_serving.py``'s harness, which runs over
both state models: a state mixer is a protocol there, not a model. Here:
the mixer's three forms against each other, the step kernel, the controls
that show each equation matters, the softmax scale, the published sizes."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.reference import granite_hybrid as ref
from tnn_tpu import models
from tnn_tpu.core.dtypes import DTypePolicy
from tnn_tpu.nn import attention as attn_lib
from tnn_tpu.ops.pallas import mamba2_step as ssm
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving.kv_pool import PagedKVPool

CFG = spec.load_json("chipbench", "configs",
                     "granite4-h-micro-serve.json")["rehearsal"]
F32 = DTypePolicy(io="float32", param="float32", compute="float32")
# The tied head over a table drawn small gives logits of deviation 0.003
# (reference/granite_hybrid.embed_std); the program in float32 lies 1e-8 from
# the float32 reference at precision "highest"
TOL = 2e-6


@pytest.fixture(scope="module")
def sz():
    return ref.sizes_of(CFG)


@pytest.fixture(scope="module")
def weights(sz):
    p = ref.make_params(sz, 49)
    return p, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def model(sz):
    m = models.create("granite4_h_micro_cpu")       # float32 by default
    ref.check_program(m, sz, "granite4_h_micro_cpu")
    return m


@pytest.fixture(scope="module")
def forward(weights, sz):
    return ref.Forward(weights[0], sz, 128)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# -- (1) the recurrence: position by position = chunked = the reference's loop ------

def _operands(seed, b, width, h=4, p=8, n=16, pad_from=None):
    """x, dt, la, bm, cm, s0 of a chunk, float32; from position
    ``pad_from`` on a row's positions are padding (``dt`` = ``la`` = 0)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, width, h, p))
    dt = np.log1p(np.exp(rng.normal(size=(b, width, h)) - 3.0))
    if pad_from is not None:
        dt[:, pad_from:] = 0.0
    la = -np.exp(rng.uniform(0.0, np.log(16.0), size=(h,))) * dt
    bm, cm = rng.normal(size=(2, b, width, n))
    s0 = rng.normal(size=(b, h, p, n))
    return [jnp.asarray(t, jnp.float32) for t in (x, dt, la, bm, cm, s0)]


def _loop(args):
    """The reference's position-by-position loop, in ``numpy`` float64."""
    x, dt, la, bm, cm, s = (np.asarray(t, np.float64) for t in args)
    ys = []
    for t in range(x.shape[1]):
        s = s * np.exp(la[:, t])[..., None, None] \
            + (dt[:, t][..., None] * x[:, t])[..., None] \
            * bm[:, t][:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", s, cm[:, t]))
    return np.stack(ys, 1), s


@pytest.mark.parametrize("width,sub,carried", [
    (64, 64, True), (128, 64, True), (16, 64, False), (48, 16, True),
    (7, 16, True), (40, 16, False), (100, 64, True), (33, 32, False)])
def test_the_three_forms_of_the_recurrence_agree(width, sub, carried):
    """Chunks that are whole sub-chunks, shorter than one, and neither (the
    mixer's ``_scan`` pads those), from a zero and from a carried state."""
    args = _operands(width, 3, width)
    if not carried:
        args[5] = jnp.zeros_like(args[5])
    want_y, want_s = _loop(args)
    s, ys = args[5], []
    for t in range(width):
        y, s = ssm.step_math(s, *(a[:, t] for a in args[:5]))
        ys.append(y)
    assert np.abs(np.asarray(jnp.stack(ys, 1)) - want_y).max() < 2e-5
    assert np.abs(np.asarray(s) - want_s).max() < 2e-5
    mix = attn_lib.Mamba2(4, 8, 16, policy=F32)
    skip = {"D": jnp.zeros((4,), jnp.float32)}
    with jax.default_matmul_precision("highest"), \
            mock.patch.object(ssm, "SUB", sub):
        got_y, got_s = ssm.ssd_chunk(*args, sub=sub) \
            if width % min(sub, width) == 0 \
            else mix._scan(skip, tuple(args[:5]), args[5])
    assert got_y.shape == want_y.shape
    assert np.abs(np.asarray(got_y) - want_y).max() < 2e-5
    assert np.abs(np.asarray(got_s) - want_s).max() < 2e-5


def test_a_ragged_chunk_needs_whole_sub_chunks():
    with pytest.raises(ValueError, match="no whole sub-chunks"):
        ssm.ssd_chunk(*_operands(0, 1, 40), sub=16)


@pytest.mark.parametrize("pad_from", [0, 5, 16, 31])
def test_padded_positions_inside_a_chunk_leave_the_state_alone(pad_from):
    args = _operands(pad_from, 2, 32, pad_from=pad_from)
    _, s_all = ssm.ssd_chunk(*args, sub=16)
    live = [a[:, :pad_from] if a.ndim > 2 and a.shape[1] == 32 else a
            for a in args]
    want = _loop(live)[1] if pad_from else np.asarray(args[5])
    assert np.abs(np.asarray(s_all) - want).max() < 2e-5


@pytest.mark.kernel
@pytest.mark.parametrize("snapshot", [True, False])
@pytest.mark.parametrize("heads", [16, 32, 4])
def test_the_step_kernel_is_the_step_and_keeps_what_it_read(heads, snapshot):
    """``tnn_mamba2_step`` (interpreted) against ``step_math``: the output,
    the live state written once, and the state a row READ copied into its
    snapshot slot where it has one; a row without keeps nothing. Heads that
    are one block of ``HEADS``, two, and fewer than one."""
    rng = np.random.default_rng(heads)
    b, p, n, layers, slots = 3, 8, 128, 2, 5
    x, dt, la, bm, cm, _ = _operands(heads, b, 1, h=heads, p=p, n=n)
    x, dt, la, bm, cm = (t[:, 0] for t in (x, dt, la, bm, cm))
    rec = jnp.asarray(rng.normal(size=(layers, slots, heads, p, n)),
                      jnp.float32)
    snap = jnp.zeros((layers, 2 * slots, heads, p, n), jnp.float32)
    at = jnp.asarray([1, 3, 0])
    keep = jnp.asarray([2, 0, 0] if snapshot else [0, 0, 0])
    want = ssm.mamba2_step(x, dt, la, bm, cm, rec, snap, at, keep, layer=1,
                           backend="xla")
    got = ssm.mamba2_step(x, dt, la, bm, cm, rec, snap, at, keep, layer=1,
                          backend="pallas", interpret=True)
    assert np.abs(np.asarray(got[0]) - np.asarray(want[0])).max() < 1e-5
    assert np.abs(np.asarray(got[1])[:, 1:] - np.asarray(want[1])[:, 1:]
                  ).max() < 1e-6
    assert np.array_equal(np.asarray(got[1][0]), np.asarray(rec[0]))
    if snapshot:
        assert np.array_equal(np.asarray(got[2][1, 2]), np.asarray(rec[1, 1]))
        assert not np.asarray(got[2][:, 3:]).any() \
            and not np.asarray(got[2][0]).any()
    else:
        assert not np.asarray(got[2][:, 1:]).any()
    with pytest.raises(ValueError, match="unknown mamba2-step backend"):
        ssm.mamba2_step(x, dt, la, bm, cm, rec, snap, at, keep, layer=1,
                        backend="cuda")


# -- (2) the mixer: a chunk boundary, the slots' protocol ----------------------------

def test_the_conv_rows_carry_across_a_chunk_boundary(weights):
    """The layer over 40 positions at once and over chunks of 16, 16 and 8
    through the cache (3 positions of ``[x | B | C]`` and the state): the
    same outputs, and the kept positions are the last three fed."""
    mix = attn_lib.Mamba2(8, 16, 16, norm_eps=1e-5, policy=F32)
    p = weights[1]["h0"]["attn"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 40, 64)),
                    jnp.float32)
    whole, _ = mix.apply({"params": p, "state": {}}, x)
    cache, outs = mix.init_cache(2, 0, 64), []
    for lo, hi in ((0, 16), (16, 32), (32, 40)):
        y, cache = mix.apply_cached({"params": p}, x[:, lo:hi], cache, lo)
        outs.append(y)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1))
                  - np.asarray(whole)).max() < 1e-5
    u = mix._project(p, x)[0]
    assert np.allclose(np.asarray(cache["conv"]), np.asarray(u[:, 37:]),
                       atol=1e-5)
    assert cache["rec"].shape == (2, 8, 16, 16)


def test_one_protocol_for_both_state_mixers():
    """What the pool and the model ask of a mixer, whichever it is:
    ``conv_rows``, ``rec_shape``, ``apply_state``; the model's ``linear``
    keywords name the class."""
    gdn = attn_lib.state_mixer(dict(key_heads=16, value_heads=32,
                                    key_dim=128, value_dim=128, conv=4))
    ssd = attn_lib.state_mixer(dict(mixer="mamba2", heads=64, head_dim=64,
                                    state=128, conv=4))
    assert type(gdn) is attn_lib.GatedDeltaNet and gdn.scope == "gdn"
    assert type(ssd) is attn_lib.Mamba2 and ssd.scope == "ssm"
    assert type(gdn).apply_state is type(ssd).apply_state
    assert (gdn.conv_rows, gdn.rec_shape) == ((48, 512), (32, 128, 128))
    # 3 x 4,352 values are 102 rows of 128 lanes, no whole (16, 128) tiles
    assert (ssd.conv_rows, ssd.rec_shape) == ((102, 128), (64, 64, 128))
    assert (ssd.channels, ssd.inner) == (4352, 4096)
    tiny = attn_lib.Mamba2(8, 16, 16)
    assert tiny.conv_rows == (3, 160)
    with pytest.raises(KeyError):
        attn_lib.state_mixer(dict(mixer="s4", heads=1))
    a, d = attn_lib._decay_init(*jax.random.split(jax.random.PRNGKey(0)),
                                512, 1.0)
    step = np.asarray(jax.nn.softplus(d))
    assert 0.0 <= float(a.min()) and float(a.max()) < np.log(16.0)
    assert 1e-3 * 0.99 < step.min() and step.max() < 0.1 * 1.01


def test_a_row_at_position_zero_ignores_what_its_slot_holds(model, weights,
                                                            forward):
    """``apply_paged`` with the state beside the pages: a slot's last
    tenant's garbage is not read by a row that starts at 0, the other slots
    are not touched, and a padding row (slot 0) writes the scratch slot."""
    pool = PagedKVPool(model.cache_layers, model.num_kv_heads, model.head_dim,
                       16, 8, dtype=jnp.float32, state=model.state_group,
                       state_rows=3)
    assert pool.slots.layers == 4 and pool.slots.nbytes == sum(
        int(x.nbytes) for x in pool.slots.arrays.values())
    state = jax.tree_util.tree_map(lambda x: x + 7.0, pool.state)
    table = np.zeros((2, 9), np.int32)
    table[1, :8], table[1, -1] = np.arange(1, 9), 2
    ids = _ids(5, 32)
    toks = np.zeros((2, 32), np.int32)
    toks[1] = ids
    lg, _, _, state = model.apply_paged(
        weights[1], jnp.asarray(toks), pool.pages_k, pool.pages_v,
        jnp.asarray(table), jnp.asarray([0, 0]), jnp.asarray([0, 32]),
        state=state)
    want = forward.rows(list(ids), np.arange(32))
    assert np.abs(np.asarray(lg[1]) - want).max() < TOL
    for name in ("rec", "conv"):
        assert np.all(np.asarray(state[name][:, 1]) == 7.0)
        assert np.all(np.asarray(state[name][:, 3]) == 7.0)
        assert not np.all(np.asarray(state[name][:, 2]) == 7.0)
    # the row started at 0, a multiple of 16: its first snapshot holds zeros
    assert not np.asarray(state["rec_snap"][:, 3]).any()
    assert np.all(np.asarray(state["rec_snap"][:, 4]) == 7.0)


# -- (3) the comparison sees each equation ---------------------------------------------

@pytest.mark.parametrize("without", ref.WITHOUT)
def test_the_comparison_sees_each_equation(weights, sz, forward, without):
    """The reference WITHOUT the ``D`` skip, the conv bias, the gate before
    the norm, each of the four multipliers (the softmax's 1/8 here, not
    ``16^-1/2``) or the decay fails the comparison the serving tests make:
    hundreds of times ``TOL``."""
    ids = _ids(7, 100)
    pos = np.arange(40, 100)
    want = forward.rows(list(ids), pos)
    got = ref.Forward(weights[0], sz, 128, without=(without,)).rows(
        list(ids), pos)
    assert np.abs(got - want).max() > 300 * TOL
    if without == "logits":
        return      # a greedy token is blind to a positive scale of logits
    # ... and the benchmark's own comparison of greedy tokens, at its limits
    limits = CFG["limits"]
    gap = want.max(-1) - want[np.arange(len(pos)), got.argmax(-1)]
    assert gap.max() > limits["gap_max"] or gap.mean() > limits["gap_mean"]


def test_the_comparison_sees_a_wrong_state(weights, sz, forward):
    """One position's state update left out, or the state kept in bfloat16
    for 512 positions, moves a logit past what the serving tests allow."""
    ids = _ids(8, 100)
    pos = np.arange(40, 100)
    want = forward.rows(list(ids), pos)
    skipped = ref.Forward(weights[0], sz, 128, skip_update=30).rows(
        list(ids), pos)
    assert np.abs(skipped - want).max() > 100 * TOL
    long = _ids(9, 512)
    pos = np.arange(256, 511)
    exact = ref.Forward(weights[0], sz, 512).rows(list(long), pos)
    low = ref.Forward(weights[0], sz, 512, quant="state_bf16").rows(
        list(long), pos)
    assert np.abs(low - exact).max() > 3 * TOL
    with pytest.raises(ValueError, match="unknown control precision"):
        ref.Forward(weights[0], sz, 128, quant="int4").rows(list(ids), pos[:1])
    with pytest.raises(ValueError, match="without names"):
        ref.Forward(weights[0], sz, 128, without=("rotary",))


# -- (4) plain attention: a softmax scale, no positions ---------------------------------

def test_the_softmax_scale_is_an_option_and_its_default_is_as_before():
    """``MultiHeadAttention(scale=)``: None is ``head_dim^-1/2`` (what GPT-2
    and EvaByte lower to does not change: the hashes of
    tests/test_qwen3_next_serving.py hold that), a value is the softmax's
    scale on the plain, the cached and the paged path alike."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 12, 64)),
                    jnp.float32)
    kw = dict(causal=True, num_kv_heads=2, use_bias=False, policy=F32)
    plain = attn_lib.MultiHeadAttention(4, **kw)
    same = attn_lib.MultiHeadAttention(4, scale=16 ** -0.5, **kw)
    wide = attn_lib.MultiHeadAttention(4, scale=0.125, **kw)
    v = plain.init(jax.random.PRNGKey(0), x.shape)
    y0, y1, y2 = (m.apply(v, x)[0] for m in (plain, same, wide))
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert np.abs(np.asarray(y0) - np.asarray(y2)).max() > 1e-3
    assert plain.scale is None and "scale" not in plain._config()
    assert wide._config()["scale"] == 0.125 and wide.rope_theta is None
    # the cached and the paged path take the same scale
    cache = wide.init_cache(1, 16, 64)
    yc, cache = wide.apply_cached(v, x, cache, 0)
    assert np.abs(np.asarray(yc) - np.asarray(y2)).max() < 1e-5
    pages = jnp.zeros((1, 4, 2, 8, 16), jnp.float32)
    yp, _, _ = wide.apply_paged(
        v, x, pages, pages, jnp.asarray([[1, 2]]), jnp.asarray([0]),
        q_lens=jnp.asarray([12]))
    assert np.abs(np.asarray(yp) - np.asarray(y2)).max() < 1e-5
    with pytest.raises(ValueError, match="no softmax scale"):
        attn_lib.MultiHeadAttention(4, window=32, chunk=4, scale=0.5)


def test_the_order_of_positions_reaches_attention_through_the_mixers(
        model, weights):
    """No rotary anywhere: the attention layer alone cannot tell two orders
    of its context apart; the model can, through the Mamba layers."""
    att = model.blocks[2].attn
    assert type(att) is attn_lib.MultiHeadAttention \
        and att.rope_theta is None and att.scale == 0.125
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 9, 64)),
                    jnp.float32)
    swapped = x.at[:, [2, 5]].set(x[:, [5, 2]])
    v = {"params": weights[1]["h2"]["attn"], "state": {}}
    a, b = att.apply(v, x)[0], att.apply(v, swapped)[0]
    assert np.abs(np.asarray(a[:, -1]) - np.asarray(b[:, -1])).max() < 1e-6
    ids = _ids(6, 9)
    other = ids.copy()
    other[[2, 5]] = ids[[5, 2]]
    la, lb = (model.apply({"params": weights[1], "state": {}},
                          jnp.asarray(t)[None])[0][0, -1]
              for t in (ids, other))
    assert np.abs(np.asarray(la) - np.asarray(lb)).max() > 100 * TOL


# -- (5) the engine: counters, the overlapped loop at depth 12 ----------------------------

def test_the_engine_serves_the_reference_logits_and_counts_the_state(
        model, weights, forward):
    """Prefill in chunks, then decode through pool, slots and the overlapped
    loop (up to 12 steps queued): at every served position the token's
    reference logit is the reference's best; ``state_bytes`` is the four
    arrays' bytes, in ``summary()`` and in the exposition."""
    eng = InferenceEngine(model, weights[1], num_blocks=96, block_size=8,
                          max_batch_size=4, chunk_size=16,
                          prefix_cache=False, max_seq_len=192, overlap=True)
    prompts = [_ids(20 + i, n) for i, n in enumerate((37, 16, 50))]
    rids = [eng.submit(p, 70) for p in prompts]
    out = eng.run_until_complete()
    eng.check_invariants()
    for rid, p in zip(rids, prompts):
        toks = out[rid]
        lg = forward.rows(list(p) + toks,
                          np.arange(len(p) - 1, len(p) + len(toks) - 1))
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert len(toks) == 70 and gap.max() < TOL
    s = eng.metrics.summary()
    assert s["state_restores"] == 0 and s["adopted_step_share"] > 0.5
    # 4 layers x (5 live + 9 snapshot slots) x (3 x 160 + 8 x 16 x 16) x 4 B
    assert s["state_bytes"] == eng.pool.slots.nbytes \
        == 4 * 14 * (480 + 2048) * 4
    assert s["state_slots_occupancy_max"] == 0.75
    names = {f["name"] for f in eng.metrics.prometheus_series()}
    assert "tnn_serve_state_bytes" in names
    plain = InferenceEngine(models.create("gpt2_tiny"), models.create(
        "gpt2_tiny").init(jax.random.PRNGKey(0), (1, 8))["params"],
        num_blocks=16, block_size=8, max_batch_size=2)
    assert "state_bytes" not in plain.metrics.summary()


def test_the_recurrent_state_stays_float32_under_a_bfloat16_program(sz):
    """The configuration states a FLOAT32 recurrent state (``assumed.state``)
    and no limit on served tokens can tell a bfloat16 one apart
    (``limits.not_held``: the ``state_bf16`` control reads a gap of 0 on the
    chip), so the dtype is held here, by structure: served in the cell's
    bfloat16, the pool's ``rec`` and ``rec_snap`` are float32 when made and
    after the step programs have handed them back, the conv rows are the
    model's dtype, and ``state_bytes`` counts them so. A PR that keeps the
    state in the model dtype has to change the configuration's stated dtype,
    its ``pinned`` arithmetic and this test together."""
    config = spec.load_json("chipbench", "configs",
                            "granite4-h-micro-serve.json")
    assert "FLOAT32" in config["assumed"]["state"]
    assert "64 x 64 x 128 float32 = 2,097,152 B" in config["pinned"]["why"]
    bf16 = DTypePolicy(io="bfloat16", param="bfloat16", compute="bfloat16")
    m = models.create("granite4_h_micro_cpu", policy=bf16)
    ref.check_program(m, sz, "granite4_h_micro_cpu")
    params = m.init(jax.random.PRNGKey(49), (1, 8))["params"]
    eng = InferenceEngine(m, params, num_blocks=32, block_size=8,
                          max_batch_size=2, chunk_size=16,
                          prefix_cache=False, max_seq_len=96, overlap=True)
    want = dict(conv=jnp.bfloat16, conv_snap=jnp.bfloat16, rec=jnp.float32,
                rec_snap=jnp.float32)
    assert {k: v.dtype for k, v in eng.pool.slots.arrays.items()} == want
    rid = eng.submit(_ids(3, 21), 40)
    assert len(eng.run_until_complete()[rid]) == 40
    assert {k: v.dtype for k, v in eng.pool.cache[2].items()} == want
    # 4 layers x (3 live + 5 snapshot slots) x (480 bf16 + 2,048 float32)
    assert eng.metrics.summary()["state_bytes"] == eng.pool.slots.nbytes \
        == 4 * 8 * (480 * 2 + 2048 * 4)


# -- (6) the served model, as published ------------------------------------------------------

def test_the_published_sizes_of_the_served_model():
    """``granite4_h_micro`` as the cell runs it: every width the source's,
    all 40 layers, the whole vocabulary."""
    m = models.create("granite4_h_micro")
    assert (m.num_layers, m.cache_layers, m.d_model, m.num_heads,
            m.num_kv_heads, m.head_dim, m.vocab_size, m.mlp_hidden) == (
                40, 4, 2048, 32, 8, 64, 100352, 8192)
    assert [i for i, k in enumerate(m.layer_types) if k == "attention"] \
        == [5, 15, 25, 35] and m.layer_types.count("mamba") == 36
    assert m.state_group == dict(layers=36, conv=(102, 128),
                                 rec=(64, 64, 128))
    assert m.page_groups is None and m.gated is None and m.experts is None
    assert m.rope_theta is None and m.tie_embeddings and m.residual_f32
    assert m.scales == dict(embedding=12.0, residual=0.22,
                            attention=0.015625, logits=8.0)
    mix, att = m.blocks[0].attn, m.blocks[5].attn
    assert (mix.heads, mix.head_dim, mix.state, mix.conv, mix.channels,
            mix.norm_eps) == (64, 64, 128, 4, 4352, 1e-5)
    assert (att.num_heads, att.num_kv_heads, att.scale, att.rope_theta,
            att.use_bias) == (32, 8, 1 / 64, None, False)
    shapes = jax.eval_shape(
        lambda: m.init(jax.random.PRNGKey(0), (1, 8))["params"])
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert n == 3_191_396_096                   # 6.38 GB of bf16
    assert "head" not in shapes
    assert shapes["h0"]["attn"]["in_kernel"].shape == (2048, 8512)
    assert shapes["h0"]["attn"]["conv_bias"].shape == (4352,)
    assert shapes["h0"]["attn"]["D"].dtype == jnp.float32
    assert shapes["h5"]["attn"]["qkv_kernel"].shape == (2048, 3072)
    assert shapes["h5"]["gate"]["kernel"].shape == (2048, 8192)
    assert m._config()["layer_types"] == m.layer_types
    # two heads of 64 share a page row under grouped queries
    from tnn_tpu.ops.pallas.paged_attention import lane_pack

    assert lane_pack(8, 64, jnp.bfloat16) == 2
    from tnn_tpu.models.llama import Llama

    with pytest.raises(ValueError, match="layer_types are gated's"):
        Llama(vocab_size=8, num_layers=1, d_model=8, num_heads=1,
              layer_types=["attention"],
              gated=dict(head_dim=8, window=None, rope_theta=1e4,
                         layer_types=["full_attention"]))
    with pytest.raises(ValueError, match="a kind for each"):
        Llama(vocab_size=8, num_layers=2, d_model=8, num_heads=1,
              layer_types=["attention"])


def test_absent_scales_are_todays_model():
    """``scales`` absent: no multiplier anywhere (the older models' lowered
    programs do not change); each present one is applied once."""
    kw = dict(vocab_size=64, max_len=32, num_layers=2, d_model=32,
              num_heads=2, mlp_hidden=64, policy=F32)
    from tnn_tpu.models.llama import Llama

    plain = Llama(**kw)
    ones = Llama(scales=dict(embedding=1.0, residual=1.0,
                                          logits=1.0), **kw)
    v = plain.init(jax.random.PRNGKey(0), (1, 8))
    ids = jnp.asarray(_ids(0, 8) % 64)[None]
    y0, y1 = plain.apply(v, ids)[0], ones.apply(v, ids)[0]
    assert np.abs(np.asarray(y0) - np.asarray(y1)).max() < 1e-6
    assert "scales" not in plain._config() \
        and "scales" not in plain.blocks[0]._config()
    half = Llama(scales=dict(logits=2.0), **kw)
    assert np.allclose(np.asarray(half.apply(v, ids)[0]) * 2.0,
                       np.asarray(y0), atol=1e-6)
    assert half._config()["scales"] == dict(logits=2.0)
