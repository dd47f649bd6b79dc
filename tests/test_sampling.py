"""Sampling strategies: greedy/temperature/top-k/top-p semantics and their
wiring through generate() (the reference's loop is greedy-only,
examples/gpt2_inference.cpp:107-119)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tnn_tpu.models.sampling import make_sampler


def test_greedy_is_argmax():
    logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 0.0]])
    s = make_sampler(0.0)
    toks = s(logits, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(toks), [1, 0])


def test_top_k_restricts_support():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(1, 50) * 3)
    top3 = set(np.asarray(jnp.argsort(logits[0])[-3:]).tolist())
    s = make_sampler(1.0, top_k=3)
    seen = {int(s(logits, jax.random.PRNGKey(i))[0]) for i in range(64)}
    assert seen <= top3 and len(seen) >= 2


def test_top_k_1_equals_greedy():
    logits = jnp.asarray(np.random.RandomState(1).randn(4, 20))
    s = make_sampler(0.7, top_k=1)
    toks = np.asarray(s(logits, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(toks, np.asarray(jnp.argmax(logits, -1)))


def test_top_p_nucleus_mass():
    # crafted distribution: probs ~ [0.5, 0.3, 0.1, 0.1]; top_p=0.7 keeps
    # exactly the first two (0.5 < 0.7, 0.8-0.3=0.5 < 0.7, 0.9-0.1=0.8 >= 0.7)
    probs = np.asarray([0.5, 0.3, 0.1, 0.1])
    logits = jnp.asarray(np.log(probs))[None]
    s = make_sampler(1.0, top_p=0.7)
    seen = {int(s(logits, jax.random.PRNGKey(i))[0]) for i in range(128)}
    assert seen == {0, 1}, seen


def test_top_p_always_keeps_best():
    logits = jnp.asarray([[10.0, 0.0, 0.0]])
    s = make_sampler(1.0, top_p=1e-6)
    for i in range(8):
        assert int(s(logits, jax.random.PRNGKey(i))[0]) == 0


def test_generate_with_sampling_runs():
    from tnn_tpu.models.gpt2 import GPT2, generate

    model = GPT2(vocab_size=128, max_len=32, num_layers=1, d_model=64,
                 num_heads=2)
    v = model.init(jax.random.PRNGKey(0), (1, 8))
    prompt = jnp.zeros((1, 4), jnp.int32)
    toks = generate(model, v["params"], prompt, 4, temperature=0.8,
                    top_k=10, top_p=0.9)
    assert toks.shape == (1, 4)
    assert ((np.asarray(toks) >= 0) & (np.asarray(toks) < 128)).all()
    # deterministic given the same rng
    toks2 = generate(model, v["params"], prompt, 4, temperature=0.8,
                     top_k=10, top_p=0.9)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))


# -- per-row (ragged) sampling: the serving engine's vectorized kernel --------


def test_ragged_matches_scalar_same_key():
    """Per-row arrays with every row at the same params must reproduce the
    scalar sampler exactly (same key, same categorical draw)."""
    from tnn_tpu.models.sampling import sample_ragged

    rs = np.random.RandomState(2)
    logits = jnp.asarray(rs.randn(4, 50) * 2)
    key = jax.random.PRNGKey(7)
    for t, k, p in [(0.0, 0, 0.0), (1.0, 0, 0.0), (0.8, 5, 0.0),
                    (1.2, 0, 0.6), (0.7, 8, 0.9)]:
        want = np.asarray(make_sampler(t, k, p)(logits, key))
        got = np.asarray(sample_ragged(
            logits, key, jnp.full((4,), t), jnp.full((4,), k, jnp.int32),
            jnp.full((4,), p)))
        np.testing.assert_array_equal(got, want, err_msg=f"t={t} k={k} p={p}")


def test_ragged_mixed_rows():
    """Greedy and stochastic rows coexist: temperature 0 rows are exact
    argmax; top-k rows stay inside their own row's k-support."""
    from tnn_tpu.models.sampling import sample_ragged

    rs = np.random.RandomState(3)
    logits = jnp.asarray(rs.randn(3, 40))
    t = jnp.asarray([0.0, 1.0, 1.0])
    k = jnp.asarray([0, 3, 0], jnp.int32)
    p = jnp.asarray([0.0, 0.0, 0.9])
    top3 = set(np.asarray(jnp.argsort(logits[1])[-3:]).tolist())
    for i in range(32):
        toks = np.asarray(sample_ragged(logits, jax.random.PRNGKey(i),
                                        t, k, p))
        assert toks[0] == int(jnp.argmax(logits[0]))
        assert int(toks[1]) in top3
        assert 0 <= int(toks[2]) < 40


def test_make_sampler_accepts_perrow_arrays():
    logits = jnp.asarray(np.random.RandomState(4).randn(2, 30))
    s = make_sampler(jnp.asarray([0.0, 1.0]), top_k=jnp.asarray([0, 4]))
    toks = np.asarray(s(logits, jax.random.PRNGKey(0)))
    assert toks[0] == int(jnp.argmax(logits[0]))
    top4 = set(np.asarray(jnp.argsort(logits[1])[-4:]).tolist())
    assert int(toks[1]) in top4


def test_ragged_jits_with_traced_params():
    """The engine passes t/k/p as TRACED arrays inside one compiled decode
    step — the kernel must not branch on their values."""
    from tnn_tpu.models.sampling import sample_ragged

    f = jax.jit(sample_ragged)
    logits = jnp.asarray(np.random.RandomState(5).randn(2, 20))
    toks = np.asarray(f(logits, jax.random.PRNGKey(0),
                        jnp.asarray([0.0, 0.9]), jnp.asarray([0, 5]),
                        jnp.asarray([0.0, 0.8])))
    assert toks.shape == (2,)
    assert toks[0] == int(jnp.argmax(logits[0]))


# -- filter_logits: the shared filtering core ---------------------------------


def test_filter_logits_matches_scalar_sampler_draws():
    """softmax(filter_logits(...)) IS the sampler's categorical
    distribution: drawing from it with the scalar path's key must reproduce
    make_sampler draw-for-draw (byte-identical filtered logits)."""
    from tnn_tpu.models.sampling import filter_logits

    rs = np.random.RandomState(6)
    logits = jnp.asarray(rs.randn(4, 50) * 2)
    for t, k, p in [(1.0, 0, 0.0), (0.8, 5, 0.0), (1.2, 0, 0.6),
                    (0.7, 8, 0.9)]:
        for i in range(8):
            key = jax.random.PRNGKey(i)
            want = np.asarray(make_sampler(t, k, p)(logits, key))
            got = np.asarray(jax.random.categorical(
                key, filter_logits(logits, t, k, p), axis=-1))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"t={t} k={k} p={p}")


def test_filter_logits_keepall_defaults_are_identity():
    """Out-of-range params degrade to keep-all: t<=0 scales by 1, k outside
    [1, V) and p outside (0, 1) filter nothing."""
    from tnn_tpu.models.sampling import filter_logits

    logits = jnp.asarray(np.random.RandomState(7).randn(3, 20), jnp.float32)
    for t, k, p in [(1.0, 0, 0.0), (0.0, 20, 1.0), (-1.0, -3, 2.0),
                    (1.0, 50, 0.0)]:
        np.testing.assert_array_equal(
            np.asarray(filter_logits(logits, t, k, p)), np.asarray(logits))
    # temperature really scales
    np.testing.assert_allclose(
        np.asarray(filter_logits(logits, 2.0, 0, 0.0)),
        np.asarray(logits) / 2.0, rtol=1e-6)


def test_filter_logits_perrow_supports():
    """Per-row params: a top-k row keeps exactly its k best tokens, a
    nucleus row keeps a probability-ordered prefix that includes the best
    token, and a default row is untouched."""
    from tnn_tpu.models.sampling import NEG_INF, filter_logits

    rs = np.random.RandomState(8)
    logits = jnp.asarray(rs.randn(3, 12))
    out = np.asarray(filter_logits(
        logits, jnp.asarray([1.0, 1.0, 1.0]),
        jnp.asarray([3, 0, 0], jnp.int32), jnp.asarray([0.0, 0.7, 0.0])))
    row0 = np.asarray(logits[0])
    kept0 = set(np.flatnonzero(out[0] > float(NEG_INF) / 2).tolist())
    assert kept0 == set(np.argsort(row0)[-3:].tolist())
    row1 = np.asarray(logits[1])
    kept1 = np.flatnonzero(out[1] > float(NEG_INF) / 2)
    dropped1 = np.setdiff1d(np.arange(12), kept1)
    assert int(row1.argmax()) in kept1.tolist()
    assert 1 <= len(kept1) < 12
    assert row1[kept1].min() > row1[dropped1].max()  # a prefix by prob
    np.testing.assert_array_equal(out[2], np.asarray(logits[2], np.float32))


# -- the sampler runs what the step's rows ask for ----------------------------
#
# The straight-line form sample_ragged and filter_logits had before the
# conditional and the shared sort: the filter sorts the vocabulary twice and
# every row is drawn whatever its temperature. Kept here as the reference the
# tokens and the filtered logits are held to, bit for bit.

def _ref_top_p(x, p):
    down = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    probs = jax.nn.softmax(down, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    cutoff = jnp.min(jnp.where((csum - probs) < p, down, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(x < cutoff, np.float32(-1e30), x)


def _ref_filter(logits, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    v, rows = logits.shape[-1], logits.shape[:-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), rows)[..., None]
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows)[..., None]
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), rows)[..., None]
    x = logits / jnp.where(t > 0.0, t, 1.0)
    k_eff = jnp.where((k > 0) & (k < v), k, v)
    down = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    kth = jnp.take_along_axis(down, k_eff - 1, axis=-1)
    x = jnp.where(x < kth, np.float32(-1e30), x)
    return _ref_top_p(x, jnp.where((p > 0.0) & (p < 1.0), p, 1.0))


def _ref_sample(logits, key, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                         logits.shape[:-1])
    x = _ref_filter(logits, temperature, top_k, top_p)
    return jnp.where(t > 0.0, jax.random.categorical(key, x, axis=-1),
                     jnp.argmax(logits, axis=-1))


def _twin_logits(seed, rows=6, v=96):
    """Logits whose columns come in twins (2j and 2j + 1 hold one value), so
    that an odd k or a nucleus edge falls BETWEEN two equal values: ties at
    the k-th value and at the nucleus' edge. Row 4 holds -1e30 entries
    already (a row filtered once), row 5 half a row of them."""
    rs = np.random.RandomState(seed)
    x = np.repeat(rs.randn(rows, v // 2).astype(np.float32) * 2.5, 2, axis=1)
    x[4, rs.permutation(v)[:7]] = -1e30
    x[5, : v // 2] = -1e30
    return jnp.asarray(x)


# greedy, temperature, top-k (odd: the k-th value has a twin), top-p, both,
# and both on the row that is half -1e30
_MIXED = dict(
    temperature=np.asarray([0.0, 0.7, 1.0, 1.3, 0.9, 1.1], np.float32),
    top_k=np.asarray([0, 0, 5, 0, 7, 3], np.int32),
    top_p=np.asarray([0.0, 0.0, 0.0, 0.6, 0.8, 0.5], np.float32))


def _computations(text):
    """An HLO module's text -> {computation's first line: its body}."""
    out = {}
    for block in text.split("\n\n"):
        block = block.strip()
        if "{" in block.split("\n", 1)[0]:
            out[block.split("\n", 1)[0]] = block
    return out


@pytest.mark.parametrize("rows,v", [(4, 64), (16, 1000)])
def test_greedy_step_is_argmax_and_skips_the_sort(rows, v):
    """A batch with no sampled row, under jit: the argmax, bit for bit; and
    the compiled program holds ONE conditional with every sort inside a
    branch of it (none at the entry: a greedy step runs none)."""
    from tnn_tpu.models.sampling import sample_ragged

    logits = jnp.asarray(np.random.RandomState(9).randn(rows, v), jnp.float32)
    zeros = jnp.zeros((rows,), jnp.float32)
    args = (logits, jax.random.PRNGKey(0), zeros,
            jnp.full((rows,), 5, jnp.int32), jnp.full((rows,), 0.5))
    f = jax.jit(sample_ragged)
    np.testing.assert_array_equal(np.asarray(f(*args)),
                                  np.asarray(jnp.argmax(logits, -1)))
    # negative temperatures are greedy rows too
    np.testing.assert_array_equal(
        np.asarray(f(logits, args[1], zeros - 1.0, *args[3:])),
        np.asarray(jnp.argmax(logits, -1)))
    comps = _computations(f.lower(*args).compile().as_text())
    entry = [body for head, body in comps.items() if head.startswith("ENTRY")]
    assert len(entry) == 1
    assert len(re.findall(r" conditional\(", entry[0])) == 1
    sorts = {head: len(re.findall(r" sort\(", body))
             for head, body in comps.items() if " sort(" in body}
    assert sum(sorts.values()) == 1, sorts       # one sort where there were 2
    assert not any(head.startswith("ENTRY") for head in sorts), sorts
    assert " conditional(" not in "".join(
        body for head, body in comps.items() if not head.startswith("ENTRY"))


@pytest.mark.parametrize("jitted", [False, True])
def test_mixed_step_returns_the_straight_line_tokens(jitted):
    """A batch that holds a sampled row takes the conditional's branch and
    returns what the straight-line form returns, for 20 keys, ties at the
    k-th value and at the nucleus' edge included."""
    from tnn_tpu.models.sampling import sample_ragged

    got_f = jax.jit(sample_ragged) if jitted else sample_ragged
    want_f = jax.jit(_ref_sample) if jitted else _ref_sample
    for seed in range(2):
        logits = _twin_logits(seed)
        for i in range(10):
            key = jax.random.PRNGKey(100 * seed + i)
            got = np.asarray(got_f(logits, key, **_MIXED))
            want = np.asarray(want_f(logits, key, **_MIXED))
            np.testing.assert_array_equal(got, want)
            assert got[0] == int(jnp.argmax(logits[0]))


def test_one_sampled_row_is_enough():
    """ONE row with a temperature among greedy rows: the step draws for it
    (the reference's token), and the greedy rows keep their argmax."""
    from tnn_tpu.models.sampling import sample_ragged

    logits = _twin_logits(3)
    t = jnp.zeros((6,), jnp.float32).at[3].set(0.8)
    k = jnp.zeros((6,), jnp.int32)
    p = jnp.zeros((6,), jnp.float32)
    f = jax.jit(sample_ragged)
    seen = set()
    for i in range(20):
        key = jax.random.PRNGKey(i)
        got = np.asarray(f(logits, key, t, k, p))
        np.testing.assert_array_equal(
            got, np.asarray(_ref_sample(logits, key, t, k, p)))
        seen.add(int(got[3]))
    assert len(seen) > 1                      # it really is a draw


@pytest.mark.parametrize("cube", [False, True])
def test_filter_logits_one_sort_is_the_two_sort_form(cube):
    """``filter_logits`` with one sort of the vocabulary returns the filtered
    logits of the two-sort form bit for bit: ties at the k-th value, a
    nucleus that ends between twins, rows that hold -1e30 already, and the
    ``(B, Q, V)`` cube ``_spec_verify`` hands it."""
    from tnn_tpu.models.sampling import filter_logits

    for seed in range(4):
        logits = _twin_logits(seed)
        t, k, p = (jnp.asarray(_MIXED[n])
                   for n in ("temperature", "top_k", "top_p"))
        if cube:
            logits = jnp.stack([logits, logits[::-1], logits * 0.5], axis=1)
            t, k, p = t[:, None], k[:, None], p[:, None]
        want = np.asarray(_ref_filter(logits, t, k, p))
        for f in (filter_logits, jax.jit(filter_logits)):
            got = np.asarray(f(logits, t, k, p))
            np.testing.assert_array_equal(got, want)
        # the filter did something on every row that asks for it
        kept = (want > -1e29).sum(-1)
        assert (kept[2:] < (np.asarray(logits) > -1e29).sum(-1)[2:]).all()
    text = jax.jit(filter_logits).lower(logits, t, k, p).compile().as_text()
    assert len(re.findall(r" sort\(", text)) == 1


class TestSampledStepShare:
    """Through the engine: a sampled request among greedy ones."""

    KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32,
              seed=11)

    @pytest.fixture(scope="class")
    def lm(self):
        from tnn_tpu.models.gpt2 import GPT2

        model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                     num_heads=2)
        return model, model.init(jax.random.PRNGKey(0), (1, 8))["params"]

    def _prompts(self):
        rng = np.random.default_rng(4)
        return [rng.integers(0, 128, n).astype(np.int32) for n in (6, 9, 5)]

    @pytest.mark.parametrize("overlap", [False, True])
    def test_each_request_gets_the_tokens_it_gets_alone(self, lm, overlap):
        """The sampled request (first: row 0, 5 tokens) beside two greedy
        ones (12 tokens): every request reads the tokens it reads alone in an
        engine of the same seed, the counter reads the share of the steps the
        sampled request lived through, and the spans say which those were."""
        from tnn_tpu.serving import InferenceEngine

        model, params = lm
        prompts = self._prompts()
        sampled = dict(temperature=0.9, top_k=16, top_p=0.9)

        def run(which, **kw):
            eng = InferenceEngine(model, params, overlap=overlap, **self.KW,
                                  **kw)
            rids = {i: eng.submit(prompts[i], 5 if i == 0 else 12,
                                  **(sampled if i == 0 else {}))
                    for i in which}
            out = eng.run_until_complete()
            eng.check_invariants()
            return {i: out[r] for i, r in rids.items()}, eng

        together, eng = run((0, 1, 2), trace=True)
        for i in range(3):
            alone, _ = run((i,))
            assert together[i] == alone[i], i
        greedy_only, eng_g = run((1, 2))
        assert greedy_only == {i: together[i] for i in (1, 2)}
        assert eng_g.metrics.summary()["sampled_step_share"] == 0.0
        assert eng_g.metrics.sampled_steps == 0
        assert eng_g.metrics.dispatched_steps > 0
        # the sampled request lives through its prefill step and 4 decode
        # steps; the greedy ones through 12, and all start in one step
        m = eng.metrics
        spans = [ev.name for ev in eng.profiler.events
                 if ev.name.startswith("serve.dispatch")]
        assert len(spans) == m.dispatched_steps
        with_row = [n for n in spans if "sampled_rows=1" in n]
        assert len(with_row) == m.sampled_steps >= 5
        assert all("sampled_rows=0" in n for n in spans if n not in with_row)
        assert m.summary()["sampled_step_share"] == pytest.approx(
            m.sampled_steps / m.dispatched_steps)
        if not overlap:
            assert (m.sampled_steps, m.dispatched_steps) == (5, 12)

    def test_share_is_zero_before_any_step(self):
        from tnn_tpu.serving.metrics import ServingMetrics

        assert ServingMetrics().summary()["sampled_step_share"] == 0.0
