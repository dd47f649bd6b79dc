"""The optimizer side of the training reference, written out: global-norm
clipping, AdamW (Loshchilov & Hutter 2019: decoupled weight decay, bias
correction) and linear warm-up into a cosine decay. Plain float32
``jax.numpy``; it imports nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def lr_scale(step, warmup, t_max):
    """Linear from 0 over ``warmup`` steps, then half a cosine to 0 at
    ``t_max``; ``step`` counts from 0."""
    if step < warmup:
        return step / max(1, warmup)
    t = min(max((step - warmup) / max(1, t_max - warmup), 0.0), 1.0)
    return 0.5 * (1.0 + math.cos(math.pi * t))


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": 0}


def update(params, grads, state, *, lr, weight_decay, b1=0.9, b2=0.999,
           eps=1e-8):
    t = state["t"] + 1
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                               state["v"], grads)

    def leaf(p, m_, v_):
        upd = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        return p - lr * upd - lr * weight_decay * p

    return jax.tree_util.tree_map(leaf, params, m, v), \
        {"m": m, "v": v, "t": t}
