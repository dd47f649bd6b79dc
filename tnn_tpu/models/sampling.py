"""Token-sampling strategies for autoregressive generation.

Greedy, temperature, top-k, and nucleus (top-p) sampling behind one factory —
shared by models.gpt2.generate and the
serving engine (tnn_tpu/serving/engine.py). Exceeds the reference, whose
inference loop is greedy argmax only (examples/gpt2_inference.cpp:107-119).

Two entry points:
  * ``make_sampler(t, k, p)`` — scalars OR per-row arrays; returns a
    ``(logits, key) -> ids`` closure. Scalar behavior is byte-for-byte the
    original implementation.
  * ``sample_ragged(logits, key, t, k, p)`` — the fully vectorized kernel the
    serving engine calls with TRACED per-request parameter arrays, so one
    compiled decode step serves any mix of greedy/temperature/top-k/top-p
    requests. It runs what the step's rows ask for: the filter, its sort of
    the whole vocabulary and the draw stand in one ``lax.cond`` on "any row
    has a temperature", so a step of greedy rows runs an argmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# large-negative beats -inf: 0*inf NaN hazards. A NumPy scalar, not a jnp one:
# a device array at import time would initialize the backend (and take the
# chip) in every process that merely imports the serving package.
NEG_INF = np.float32(-1e30)


def _is_perrow(x) -> bool:
    return getattr(x, "ndim", 0) > 0


def _sort_down(x):
    return jnp.flip(jnp.sort(x, axis=-1), axis=-1)


def _top_p_filter(x, p, down=None):
    """Nucleus filter over already-scaled logits: a token survives if the
    probability mass BEFORE it is still below ``p`` — the highest-probability
    token always survives. ``p`` is a python float (scalar path) or an array
    broadcastable to x.shape[:-1] + (1,) (ragged path); values outside (0, 1)
    must already be mapped to keep-all by the caller. ``down``: ``x``'s rows
    in descending order, where the caller has them already."""
    if down is None:
        down = _sort_down(x)
    probs = jax.nn.softmax(down, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = (csum - probs) < p
    cutoff = jnp.min(jnp.where(keep, down, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(x < cutoff, NEG_INF, x)


def filter_logits(logits, temperature, top_k, top_p):
    """Temperature-scale then top-k/top-p filter, fully vectorized with
    per-row parameters; returns float32 filtered logits.

    ``softmax(filter_logits(...))`` IS the categorical distribution the
    sampler draws from, which is why this is a public helper: besides
    ``sample_ragged``, the serving engine's speculative-decoding rejection
    sampler needs the target distribution itself (to accept a drafted token
    with its target probability and to renormalize the residual), not just
    one draw from it.

    logits: (..., V); temperature/top_k/top_p: scalars or arrays broadcastable
    to logits.shape[:-1]. Per row: temperature<=0 -> scale by 1 (callers
    treat those rows as greedy); top_k<=0 or >=V -> keep-all; top_p outside
    (0, 1) -> keep-all. Filters compose (top-k first, then top-p over the
    survivors).
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    rows = logits.shape[:-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), rows)[..., None]
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows)[..., None]
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), rows)[..., None]

    x = logits / jnp.where(t > 0.0, t, 1.0)
    # top-k: the kth-largest value is the row's cutoff; k outside [1, V)
    # degrades to keep-all (cutoff = the minimum)
    k_eff = jnp.where((k > 0) & (k < v), k, v)
    down = _sort_down(x)
    kth = jnp.take_along_axis(down, k_eff - 1, axis=-1)
    x = jnp.where(x < kth, NEG_INF, x)
    # top-p over the top-k survivors. The one sort serves both filters: the
    # same cut of the sorted row IS the sort of the cut row (ties at the
    # k-th value survive in both; what falls becomes NEG_INF at the tail)
    down = jnp.where(down < kth, NEG_INF, down)
    p_eff = jnp.where((p > 0.0) & (p < 1.0), p, 1.0)
    return _top_p_filter(x, p_eff, down)


@jax.named_scope("sample")
def sample_ragged(logits, key, temperature, top_k, top_p):
    """Vectorized sampling with per-row parameters.

    logits: (..., V); temperature/top_k/top_p: scalars or arrays broadcastable
    to logits.shape[:-1]. Per row: temperature<=0 -> greedy argmax; top_k<=0
    or >=V -> keep-all; top_p outside (0, 1) -> keep-all. Filters compose as
    in the scalar path (top-k first, then top-p over the survivors).

    The filter (a sort of the whole vocabulary), the softmax and the draw run
    only in a step that holds a row with a temperature: what decides is the
    step's own input, so one compiled program serves both kinds of step. A
    step with such a row returns what the straight-line form returns, bit for
    bit (the key is consumed the same way).
    """
    logits = logits.astype(jnp.float32)
    rows = logits.shape[:-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), rows)

    greedy = jnp.argmax(logits, axis=-1)

    def draw():
        x = filter_logits(logits, temperature, top_k, top_p)
        sampled = jax.random.categorical(key, x, axis=-1)
        return jnp.where(t > 0.0, sampled, greedy)

    return jax.lax.cond(jnp.any(t > 0.0), draw, lambda: greedy)


def make_sampler(temperature=0.0, top_k=0, top_p=0.0):
    """Build a ``(logits (..., V), key) -> (...,) int32`` sampler.

    Scalars: temperature<=0 -> greedy argmax (top_k/top_p ignored). Otherwise
    scale by temperature, then optionally keep only the k highest logits
    (top_k>0) and/or the smallest set of tokens whose cumulative probability
    reaches top_p (0<top_p<1, "nucleus"); sample categorically from what is
    left. The filters compose (top-k first, then top-p over the survivors).

    Any parameter may instead be a per-row ARRAY (shape broadcastable to the
    logits' row dims) — per-request sampling params in one batched decode
    step; rows with temperature<=0 stay greedy.
    """
    if any(_is_perrow(x) for x in (temperature, top_k, top_p)):
        t = jnp.asarray(temperature, jnp.float32)
        k = jnp.asarray(top_k, jnp.int32)
        p = jnp.asarray(top_p, jnp.float32)

        def ragged(logits, key):
            return sample_ragged(logits, key, t, k, p)
        return ragged

    temperature = float(temperature)
    top_k = int(top_k)
    top_p = float(top_p)
    if top_p >= 1.0:
        top_p = 0.0  # keep-everything is a no-op

    if temperature <= 0.0:
        def greedy(logits, key):
            return jnp.argmax(logits, axis=-1)
        return greedy

    def sample(logits, key):
        logits = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            k = min(top_k, logits.shape[-1])  # k > V degrades to keep-all
            kth = jax.lax.top_k(logits, k)[0][..., -1:]
            logits = jnp.where(logits < kth, NEG_INF, logits)
        if top_p > 0.0:
            logits = _top_p_filter(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1)

    return sample
