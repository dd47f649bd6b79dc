"""Normalization layers: BatchNorm, LayerNorm, GroupNorm, RMSNorm.

Parity: reference norm family (~2500 LoC of NCHW/NHWC CPU+CUDA+cuDNN kernels,
layers_impl/*norm*). On TPU each is a handful of fused HLO ops; stats are computed in f32
regardless of io dtype. BatchNorm running stats live in the ``state`` collection — the
functional replacement for the reference's mutable layer members.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.module import Module, register_module


@register_module("batchnorm")
class BatchNorm(Module):
    """Batch normalization over all axes except the last (channels-last).

    Works for (N, C) and (N, H, W, C). Parity: BatchNormLayer (NCHW+NHWC CPU, CUDA,
    cuDNN variants in the reference).
    """

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5, affine: bool = True,
                 name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.momentum = float(momentum)
        self.eps = float(eps)
        self.affine = bool(affine)

    def _init(self, rng, input_shape):
        c = input_shape[-1]
        params = {}
        if self.affine:
            params = {"scale": jnp.ones((c,), self.policy.param_dtype),
                      "bias": jnp.zeros((c,), self.policy.param_dtype)}
        state = {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)}
        return params, state

    def _apply(self, params, state, x, *, train, rng):
        reduce_axes = tuple(range(x.ndim - 1))
        xf = x.astype(jnp.float32)
        if train:
            # E[x^2] - mean^2 instead of jnp.var: the two reductions have no
            # data dependence, so XLA fuses them into ONE pass over the
            # activation (jnp.var's (x - mean)^2 needs mean first — a second
            # full read). f32 accumulation; clamp absorbs the cancellation
            # residue. This is the BN-bandwidth lever on a HBM-bound step.
            mean = jnp.mean(xf, axis=reduce_axes)
            mean2 = jnp.mean(jnp.square(xf), axis=reduce_axes)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = jnp.reciprocal(jnp.sqrt(var + self.eps))
        y = (xf - mean) * inv
        if self.affine:
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype), new_state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        return {"momentum": self.momentum, "eps": self.eps, "affine": self.affine}


@register_module("layernorm")
class LayerNorm(Module):
    """Layer norm over the last dim. Parity: LayerNormLayer (CPU/CUDA/cuDNN)."""

    def __init__(self, eps: float = 1e-5, affine: bool = True, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.eps = float(eps)
        self.affine = bool(affine)

    def _init(self, rng, input_shape):
        c = input_shape[-1]
        params = {}
        if self.affine:
            params = {"scale": jnp.ones((c,), self.policy.param_dtype),
                      "bias": jnp.zeros((c,), self.policy.param_dtype)}
        return params, {}

    def _apply(self, params, state, x, *, train, rng):
        xf = x.astype(jnp.float32)
        # single-pass stats (see BatchNorm): mean and E[x^2] fuse into one read
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        mean2 = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
        y = (xf - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))
        if self.affine:
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype), state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        return {"eps": self.eps, "affine": self.affine}


@register_module("groupnorm")
class GroupNorm(Module):
    """Group norm over channel groups (channels-last). Parity: GroupNormLayer (CPU/CUDA)."""

    def __init__(self, groups: int = 32, eps: float = 1e-5, affine: bool = True,
                 name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.groups = int(groups)
        self.eps = float(eps)
        self.affine = bool(affine)

    def _init(self, rng, input_shape):
        c = input_shape[-1]
        if c % self.groups:
            raise ValueError(f"channels {c} not divisible by groups {self.groups}")
        params = {}
        if self.affine:
            params = {"scale": jnp.ones((c,), self.policy.param_dtype),
                      "bias": jnp.zeros((c,), self.policy.param_dtype)}
        return params, {}

    def _apply(self, params, state, x, *, train, rng):
        c = x.shape[-1]
        g = self.groups
        xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (g, c // g))
        axes = tuple(range(1, xf.ndim - 2)) + (xf.ndim - 1,)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        # single-pass stats (see BatchNorm)
        mean2 = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
        var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
        y = ((xf - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))).reshape(x.shape)
        if self.affine:
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype), state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        return {"groups": self.groups, "eps": self.eps, "affine": self.affine}


@register_module("rmsnorm")
class RMSNorm(Module):
    """RMS norm (no reference equivalent — modern LLM addition beyond parity).

    ``unit_offset``: the gain is ``1 + scale`` and ``scale`` starts at zero
    (EvaByte's ``norm_add_unit_offset``; Gemma's norm too)."""

    def __init__(self, eps: float = 1e-6, unit_offset: bool = False,
                 name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.eps = float(eps)
        self.unit_offset = bool(unit_offset)

    def _init(self, rng, input_shape):
        c = input_shape[-1]
        fill = jnp.zeros if self.unit_offset else jnp.ones
        return {"scale": fill((c,), self.policy.param_dtype)}, {}

    def _apply(self, params, state, x, *, train, rng):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        gain = params["scale"].astype(jnp.float32)
        if self.unit_offset:
            gain = 1.0 + gain
        y = xf * jnp.reciprocal(jnp.sqrt(ms + self.eps)) * gain
        return y.astype(x.dtype), state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"eps": self.eps}
        if self.unit_offset:
            cfg["unit_offset"] = True
        return cfg
