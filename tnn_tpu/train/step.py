"""Compiled train/eval steps.

This is the TPU-first replacement for the reference's eager per-batch hot loop
(src/nn/train.cpp:150-206: forward -> loss -> gradient -> backward -> optimizer step ->
flow sync). Here the ENTIRE step — forward, loss, backward (jax.grad), optimizer update,
metric — is one XLA program, compiled once and cached, with buffer donation so params and
optimizer state update in place on device (the reference's GraphContext slab residency,
include/nn/graph_context.hpp:37-89, maps to donated device buffers).

TrainState is the step carry: params + optimizer state + mutable net state (BatchNorm
stats) + step counter + rng key.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import losses as losses_lib
from ..nn import metrics as metrics_lib
from ..nn.optimizers import Optimizer
from ..nn.schedulers import Scheduler, NoOp
from ..profiling.profiler import span


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    net_state: Any
    step: jax.Array
    rng: jax.Array


def aux_loss_sum(net_state) -> jax.Array:
    """Sum every "aux_loss" leaf a layer reported through its mutable state —
    the channel MoE layers use for their load-balancing term (nn/moe.py). A
    model with no such leaves contributes exactly 0."""
    total = jnp.zeros((), jnp.float32)
    flat, _ = jax.tree_util.tree_flatten_with_path(net_state)
    for path, leaf in flat:
        if path and getattr(path[-1], "key", None) == "aux_loss":
            total = total + leaf.astype(jnp.float32)
    return total


def create_train_state(model, optimizer: Optimizer, rng: jax.Array, input_shape,
                       input_dtype=None) -> TrainState:
    init_rng, step_rng = jax.random.split(rng)
    if input_dtype is not None:
        variables = model.init(init_rng, input_shape, input_dtype=input_dtype)
    else:
        variables = model.init(init_rng, input_shape)
    return TrainState(
        params=variables["params"],
        opt_state=optimizer.init(variables["params"]),
        net_state=variables["state"],
        step=jnp.zeros((), jnp.int32),
        rng=step_rng,
    )


def resolve_remat_policy(remat):
    """Map a ``remat`` value to a jax.checkpoint policy (None = recompute
    everything). Shared by the single-device step and the pipeline so a
    policy name means the same thing — and a typo raises — on every path."""
    if remat is True or remat in ("full", "true"):
        return None
    policies = {
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # factory: returns the policy configured for HBM -> host offload
        "offload_dots": jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"),
    }
    if remat not in policies:
        raise ValueError(f"unknown remat policy {remat!r}; choose "
                         f"from {sorted(policies)} or True/'full'")
    return policies[remat]


def make_train_step(
    model,
    optimizer: Optimizer,
    loss_fn: Callable | str = "softmax_cross_entropy",
    scheduler: Optional[Scheduler] = None,
    compute_accuracy: bool = True,
    donate: bool = True,
    grad_accum: int = 1,
    augment: Optional[Callable] = None,
    remat: "bool | str" = False,
    lm_head_chunk: Optional[int] = None,
    steps_per_call: int = 1,
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build a jitted (state, data, labels) -> (state, metrics) step.

    The scheduler's scale is traced from the step counter, so LR schedules do not
    retrigger compilation.

    ``grad_accum`` > 1 splits the batch into that many microbatches inside the compiled
    program (lax.scan), averaging grads before ONE optimizer update — the single-process
    analog of the reference's microbatch gradient accumulation
    (gradient_accumulation_steps, src/nn/train.cpp:176-199), with peak activation
    memory divided by the accumulation factor.

    ``augment`` is an on-device ``(rng, data) -> data`` transform (an
    AugmentationPipeline.apply); fusing it into the step keeps augmentation off the
    host (the reference runs augmentation on CPU inside the loader).

    ``remat`` rematerializes the forward in the backward (jax.checkpoint
    around model.apply): activations are recomputed instead of stored, trading
    ~1/3 more FLOPs for a large cut in peak HBM — the knob that lets long-
    context/large-batch configs fit (numerically identical, tested). Beyond
    True (recompute everything), a policy name picks the middle grounds:
    "dots" (jax.checkpoint_policies.dots_saveable) keeps MXU outputs and
    recomputes only the cheap elementwise chains — most of the memory win
    for almost no extra FLOPs; "dots_no_batch" additionally drops batch-dim
    dot outputs (closer to full remat); "offload_dots" offloads the no-batch
    dot outputs to host instead of recomputing (HBM -> DCN tradeoff).

    ``lm_head_chunk``: for LM models exposing ``apply_hidden``/``head_table``
    (GPT-2), compute the loss with nn.lm_loss.lm_head_loss — the streaming
    logsumexp over vocab chunks that never materializes (tokens, vocab) f32
    logits (the largest tensor in LM training). Replaces ``loss_fn``; logits
    do not exist, so requires compute_accuracy=False.

    ``steps_per_call`` > 1 runs that many optimizer steps in ONE dispatch via
    lax.scan: the returned function takes (W, B, ...) data/labels and returns
    mean metrics plus a per-step ``loss_trace``. This exists because each
    dispatch pays a host->device round trip, which dominates small models
    when the backend is remote (the round-4 "28k tok/s tiny
    model vs 116k synthetic GPT-2-small" cliff was exactly this per-step
    latency; the synthetic bench loops on device and syncs once). Host-driven
    schedulers see one scale per call, not per step.
    """
    if lm_head_chunk is not None:
        if compute_accuracy:
            raise ValueError("lm_head_chunk computes no logits; pass "
                             "compute_accuracy=False")
        if not (hasattr(model, "apply_hidden") and hasattr(model, "head_table")):
            raise ValueError(f"{type(model).__name__} lacks apply_hidden/"
                             "head_table; lm_head_chunk needs an LM model")
    if isinstance(loss_fn, (str, dict)):
        loss_fn = losses_lib.get(loss_fn)
    scheduler = scheduler or NoOp()
    host_driven = getattr(scheduler, "host_driven", False)
    grad_accum = int(grad_accum)

    if lm_head_chunk is None:
        def apply_model(params, net_state, data, sub):
            return model.apply({"params": params, "state": net_state}, data,
                               train=True, rng=sub)
    else:
        def apply_model(params, net_state, data, sub):
            return model.apply_hidden({"params": params, "state": net_state},
                                      data, train=True, rng=sub)

    if remat:
        policy = resolve_remat_policy(remat)
        if policy is None:
            apply_model = jax.checkpoint(apply_model)
        else:
            apply_model = jax.checkpoint(apply_model, policy=policy)

    def compute_loss(params, net_state, data, labels, sub):
        out, new_net_state = apply_model(params, net_state, data, sub)
        with jax.named_scope("loss"):
            if lm_head_chunk is not None:
                from ..nn.lm_loss import lm_head_loss

                loss = lm_head_loss(out, model.head_table(params), labels,
                                    lm_head_chunk)
            else:
                loss = loss_fn(out, labels)
            loss = loss + aux_loss_sum(new_net_state)
        return loss, (out, new_net_state)

    def step(state: TrainState, data, labels, lr_scale):
        rng, aug_rng, sub = jax.random.split(state.rng, 3)
        if augment is not None:
            data = augment(aug_rng, data)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
        if grad_accum == 1:
            (loss, (out, new_net_state)), grads = grad_fn(
                state.params, state.net_state, data, labels, sub)
            with jax.named_scope("metrics"):
                acc = (metrics_lib.accuracy(out, labels)
                       if compute_accuracy else None)
        else:
            if data.shape[0] % grad_accum:
                raise ValueError(
                    f"batch size {data.shape[0]} not divisible by "
                    f"grad_accum {grad_accum}")
            n = data.shape[0] // grad_accum
            mb_data = data.reshape((grad_accum, n) + data.shape[1:])
            mb_labels = labels.reshape((grad_accum, n) + labels.shape[1:])
            subkeys = jax.random.split(sub, grad_accum)

            def mb_step(carry, mb):
                grads_acc, net_state, loss_acc, acc_acc = carry
                d, l, k = mb
                (loss, (out, net_state)), grads = grad_fn(
                    state.params, net_state, d, l, k)
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
                with jax.named_scope("metrics"):
                    acc_inc = (metrics_lib.accuracy(out, l) if compute_accuracy
                               else jnp.zeros((), jnp.float32))
                return (grads_acc, net_state, loss_acc + loss, acc_acc + acc_inc), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            init = (zeros, state.net_state, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32))
            (grads, new_net_state, loss, acc), _ = jax.lax.scan(
                mb_step, init, (mb_data, mb_labels, subkeys))
            inv = 1.0 / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss, acc = loss * inv, acc * inv

        if not host_driven:
            lr_scale = scheduler.scale(state.step)
        with jax.named_scope("optimizer"):
            new_params, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params, lr_scale=lr_scale)
        metrics = {"loss": loss, "lr_scale": lr_scale}
        if compute_accuracy:
            metrics["accuracy"] = acc
        new_state = TrainState(new_params, new_opt_state, new_net_state, state.step + 1, rng)
        return new_state, metrics

    steps_per_call = int(steps_per_call)
    if steps_per_call > 1:
        base_step = step

        def step(state: TrainState, data, labels, lr_scale):  # noqa: F811
            def body(st, xs):
                st, m = base_step(st, xs[0], xs[1], lr_scale)
                return st, m

            state, ms = jax.lax.scan(body, state, (data, labels))
            metrics = {k: jnp.mean(v) for k, v in ms.items()}
            metrics["loss_trace"] = ms["loss"]
            return state, metrics

    donate_argnums = (0,) if donate else ()
    step.__name__ = "tnn_train_step"    # the profile's module: jit_tnn_train_step
    jitted = jax.jit(step, donate_argnums=donate_argnums)

    # ``train.dispatch`` is the ENQUEUE of one compiled call (the device runs
    # it asynchronously), on the JAX profiler's clock: docs/observability.md
    if host_driven:
        # Host-driven schedulers (ReduceLROnPlateau) feed their factor in as a runtime
        # operand — tracing scheduler.scale() would constant-fold it into the program.
        def wrapped(state, data, labels):
            with span("train.dispatch"):
                return jitted(state, data, labels,
                              jnp.asarray(scheduler.current_scale(),
                                          jnp.float32))
    else:
        one = jnp.ones((), jnp.float32)  # hoisted: no per-step H2D transfer

        def wrapped(state, data, labels):
            with span("train.dispatch"):
                return jitted(state, data, labels, one)

    return wrapped


def make_eval_step(model, loss_fn: Callable | str = "softmax_cross_entropy",
                   compute_accuracy: bool = True):
    """Jitted (state, data, labels) -> metrics (no state mutation; BN uses running stats)."""
    if isinstance(loss_fn, (str, dict)):
        loss_fn = losses_lib.get(loss_fn)

    @jax.jit
    def step(state: TrainState, data, labels):
        out, _ = model.apply({"params": state.params, "state": state.net_state},
                             data, train=False)
        metrics = {"loss": loss_fn(out, labels)}
        if compute_accuracy:
            metrics["corrects"] = metrics_lib.class_corrects(out, labels)
        return metrics

    return step


def make_predict(model):
    """Jitted (params, net_state, data) -> logits — inference needs no TrainState."""

    @jax.jit
    def predict(params, net_state, data):
        out, _ = model.apply({"params": params, "state": net_state},
                             data, train=False)
        return out

    return predict
