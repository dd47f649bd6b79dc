"""Optimizers: SGD(+momentum), Adam, AdamW — functional, fused into the train step.

Parity: reference Optimizer hierarchy (include/nn/optimizers.hpp:34-48 ``attach``, SGD :70,
Adam :149 with AMSGrad option, OptimizerFactory :247; fused CPU/CUDA update kernels in
optimizers_impl/). TPU-first: the update is pure pytree math that XLA fuses into the
compiled train step, and state lives device-resident across steps (the reference's
``attach``-to-GraphContext binding becomes "state is part of the step carry").

API:
    opt = Adam(lr=1e-3)
    state = opt.init(params)
    params, state = opt.update(grads, state, params, lr_scale=1.0)

``lr_scale`` lets an LR scheduler modulate the base lr inside jit.
A string factory mirrors OptimizerFactory for config round-trip.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_REGISTRY: Dict[str, type] = {}


def register(name: str):
    def wrap(cls):
        _REGISTRY[name] = cls
        cls.opt_name = name
        return cls

    return wrap


def from_config(cfg: Dict[str, Any]) -> "Optimizer":
    """Parity: OptimizerFactory (include/nn/optimizers.hpp:247)."""
    cfg = dict(cfg)
    name = cfg.pop("type")
    if name not in _REGISTRY:
        raise KeyError(f"unknown optimizer {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**cfg)


def _tree_map(fn, *trees):
    return jax.tree_util.tree_map(fn, *trees)


class Optimizer:
    opt_name = "base"

    def __init__(self, lr: float = 1e-3, weight_decay: float = 0.0,
                 grad_clip_norm: Optional[float] = None):
        self.lr = float(lr)
        self.weight_decay = float(weight_decay)
        self.grad_clip_norm = grad_clip_norm if grad_clip_norm is None else float(grad_clip_norm)

    # -- state ---------------------------------------------------------------
    def init(self, params) -> Dict[str, Any]:
        state = self._init(params)
        state["step"] = jnp.zeros((), jnp.int32)
        return state

    def _init(self, params) -> Dict[str, Any]:
        return {}

    # -- update --------------------------------------------------------------
    def update(self, grads, state, params, lr_scale=1.0) -> Tuple[Any, Dict[str, Any]]:
        """Returns (new_params, new_state). Pure; call inside jit."""
        grads = _tree_map(lambda g, p: g.astype(jnp.float32), grads, params)
        if self.grad_clip_norm is not None:
            with jax.named_scope("grad_clip"):
                grads = clip_by_global_norm(grads, self.grad_clip_norm)
        step = state["step"] + 1
        lr = self.lr * lr_scale
        new_params, new_state = self._update(grads, state, params, lr, step)
        new_state["step"] = step
        return new_params, new_state

    def _update(self, grads, state, params, lr, step):
        raise NotImplementedError

    def get_config(self) -> Dict[str, Any]:
        cfg = {"type": self.opt_name, "lr": self.lr, "weight_decay": self.weight_decay}
        if self.grad_clip_norm is not None:
            cfg["grad_clip_norm"] = self.grad_clip_norm
        cfg.update(self._config())
        return cfg

    def _config(self):
        return {}


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return _tree_map(lambda g: g * scale, grads)


@register("sgd")
class SGD(Optimizer):
    """SGD with optional momentum/nesterov (parity: reference SGD, optimizers.hpp:70)."""

    def __init__(self, lr: float = 0.01, momentum: float = 0.0, nesterov: bool = False,
                 weight_decay: float = 0.0, grad_clip_norm=None):
        super().__init__(lr=lr, weight_decay=weight_decay, grad_clip_norm=grad_clip_norm)
        self.momentum = float(momentum)
        self.nesterov = bool(nesterov)

    def _init(self, params):
        if self.momentum == 0.0:
            return {}
        return {"velocity": _tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def _update(self, grads, state, params, lr, step):
        wd = self.weight_decay
        if wd:
            grads = _tree_map(lambda g, p: g + wd * p.astype(jnp.float32), grads, params)
        if self.momentum == 0.0:
            new_params = _tree_map(lambda p, g: (p.astype(jnp.float32) - lr * g).astype(p.dtype),
                                   params, grads)
            return new_params, {}
        mu = self.momentum
        vel = _tree_map(lambda v, g: mu * v + g, state["velocity"], grads)
        if self.nesterov:
            upd = _tree_map(lambda g, v: g + mu * v, grads, vel)
        else:
            upd = vel
        new_params = _tree_map(lambda p, u: (p.astype(jnp.float32) - lr * u).astype(p.dtype),
                               params, upd)
        return new_params, {"velocity": vel}

    def _config(self):
        return {"momentum": self.momentum, "nesterov": self.nesterov}


@register("adam")
class Adam(Optimizer):
    """Adam with bias correction + optional AMSGrad (parity: reference Adam,
    optimizers.hpp:149). ``weight_decay`` here is L2-into-grad (classic Adam)."""

    def __init__(self, lr: float = 1e-3, beta1: float = 0.9, beta2: float = 0.999,
                 eps: float = 1e-8, amsgrad: bool = False, weight_decay: float = 0.0,
                 grad_clip_norm=None):
        super().__init__(lr=lr, weight_decay=weight_decay, grad_clip_norm=grad_clip_norm)
        self.beta1 = float(beta1)
        self.beta2 = float(beta2)
        self.eps = float(eps)
        self.amsgrad = bool(amsgrad)

    def _init(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        state = {"m": _tree_map(zeros, params), "v": _tree_map(zeros, params)}
        if self.amsgrad:
            state["vmax"] = _tree_map(zeros, params)
        return state

    def _decoupled(self):
        return False

    def _update(self, grads, state, params, lr, step):
        b1, b2, eps = self.beta1, self.beta2, self.eps
        if self.weight_decay and not self._decoupled():
            grads = _tree_map(lambda g, p: g + self.weight_decay * p.astype(jnp.float32),
                              grads, params)
        m = _tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
        v = _tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
        t = step.astype(jnp.float32)
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        new_state = {"m": m, "v": v}
        if self.amsgrad:
            vmax = _tree_map(jnp.maximum, state["vmax"], v)
            new_state["vmax"] = vmax
            vhat = vmax
        else:
            vhat = v

        def step_fn(p, m_, v_):
            mhat = m_ / bc1
            vh = v_ / bc2
            upd = mhat / (jnp.sqrt(vh) + eps)
            pf = p.astype(jnp.float32) - lr * upd
            if self.weight_decay and self._decoupled():
                pf = pf - lr * self.weight_decay * p.astype(jnp.float32)
            return pf.astype(p.dtype)

        new_params = _tree_map(step_fn, params, m, vhat)
        return new_params, new_state

    def _config(self):
        return {"beta1": self.beta1, "beta2": self.beta2, "eps": self.eps,
                "amsgrad": self.amsgrad}


@register("adamw")
class AdamW(Adam):
    """Decoupled weight decay (beyond the reference inventory; standard for transformers)."""

    def _decoupled(self):
        return True
