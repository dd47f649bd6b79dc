"""What decides ``correct`` for a run of the ``train_lm`` driver.

After the window has closed and the program's state is freed, the plain
reference (``reference/gpt2.py`` + ``reference/adamw.py``: float32, precision
"highest") follows the program's first three steps from the same weights on
the same three batches, gradients summed row by row so that it fits. Compared,
each against its own limit in the configuration file (``limits``):

  loss_gap     |program's loss - reference's|, the worst of the three steps
  grad_gap     the first gradient as the optimizer got it (from AdamW's first
               moment after one step), per leaf: the gap between the program's
               norm and the reference's over the reference's norm of that
               leaf or of the median leaf, whichever is larger; the worst leaf
  delta_gap    the same for the parameters' change after the three steps
  grad_err     of a sample of that first gradient's elements (every vector
               whole, the first rows of every matrix): the norm of the
               difference from the reference's over the reference's norm.
               Norms of whole leaves hardly notice rounding (its effect on a
               norm is of second order: int8 moves ``grad_gap`` no more than
               bf16 does, PERF.md); the elements themselves do.
"""
from __future__ import annotations

import statistics

import numpy as np


def reference_steps(obs, quant=None):
    """{"loss": [3], "grad_norms": tree, "delta_norms": tree} of the plain
    reference (or of the control, computed in ``quant``)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import adamw

    ctx, ref, sz = obs["ctx"], obs["reference"], obs["sizes"]
    part = ctx.config["rehearsal"] if ctx.rehearse else ctx.config
    tr = part["train"]
    p0 = ref.make_params(sz, ctx.seed)
    params, state = p0, adamw.init(p0)
    row = jax.jit(jax.value_and_grad(
        lambda p, ids, lab: ref.loss(p, ids, lab, sz, quant)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    out = {"loss": []}
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
    for s, (data, labels) in enumerate(obs["fed"]):
        total, grads = 0.0, None
        for b in range(data.shape[0]):          # row by row: it fits
            nll, g = row(params, jnp.asarray(data[b]), jnp.asarray(labels[b]))
            total += float(nll)
            grads = g if grads is None else add(grads, g)
        count = data.size
        out["loss"].append(total / count)
        grads = jax.tree_util.tree_map(lambda g: g / count, grads)
        grads = jax.jit(adamw.clip, static_argnums=1)(
            grads, tr["grad_clip_norm"])
        if s == 0:
            from chipbench.drivers.train_lm import grad_sample

            out["grad_norms"] = jax.tree_util.tree_map(float, norms(grads))
            out["grad_sample"] = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32), grad_sample(grads))
        lr = tr["lr"] * adamw.lr_scale(s, **obs["schedule"])
        params, state = adamw.update(params, grads, state, lr=lr,
                                     weight_decay=tr["weight_decay"])
    out["delta_norms"] = jax.tree_util.tree_map(float, norms(
        jax.tree_util.tree_map(jnp.subtract, params, p0)))
    return out


def worst_leaf_gap(got, want):
    """max over leaves of |got - want| / max(want, median of want)."""
    import jax

    g = np.asarray(jax.tree_util.tree_leaves(got), np.float64)
    w = np.asarray(jax.tree_util.tree_leaves(want), np.float64)
    floor = statistics.median(w.tolist())
    return float(np.max(np.abs(g - w) / np.maximum(w, floor)))


def sample_error(got, want):
    """||got - want|| / ||want|| over all sampled elements."""
    import jax

    g = np.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(got)])
    w = np.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(g.astype(np.float64) - w)
                 / np.linalg.norm(w.astype(np.float64)))


def gaps(got, want):
    return {
        "grad_err": sample_error(got["grad_sample"], want["grad_sample"]),
        "loss_gap": max(abs(a - b) for a, b in zip(got["loss"], want["loss"])),
        "grad_gap": worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
        "delta_gap": worst_leaf_gap(got["delta_norms"], want["delta_norms"]),
    }


def judge(obs):
    ctx = obs["ctx"]
    hold = ctx.hold

    hold("steps", obs["steps_in_window"], ">= 1",
         obs["steps_in_window"] >= 1, "steps finished in the window")
    hold("nonfinite_losses", obs["nonfinite"], 0, obs["nonfinite"] == 0,
         "of those fetched in the window")
    hold("final_loss", obs["final_loss"], "finite",
         bool(np.isfinite(obs["final_loss"])), "after the window")
    want = reference_steps(obs)
    ctx.note(f"reference losses {want['loss']}; program's {obs['got']['loss']}")
    obs["readings"] = gaps(obs["got"], want)
    limits = (ctx.config["rehearsal"] if ctx.rehearse
              else ctx.config)["limits"]
    for name, value in obs["readings"].items():
        hold(name, value, limits[name],
             bool(np.isfinite(value)) and value <= limits[name])
    from chipbench.opcount import lm_train

    rate = obs["steps_in_window"] * obs["batch"] * obs["seq"] / obs["window_s"]
    obs["facts"] = {"model_flops_per_s":
                    rate * lm_train.flops_per_token(obs["sizes"], obs["seq"])}
    correct = all(c["ok"] for c in ctx.checks.values())
    return correct, obs["steps_in_window"], obs["nonfinite"]


def also_worth_reading(obs):
    from chipbench import stats

    xs = [1e3 * x for x in obs["spans"]["input"]]
    yield (f"steps in window {obs['steps_in_window']}, step ms "
           f"{1e3 * obs['window_s'] / max(1, obs['steps_in_window'])}, "
           f"tokens/s {obs['steps_in_window'] * obs['batch'] * obs['seq'] / obs['window_s']}")
    yield (f"input span ms: p50 {stats.percentile(xs, 50)} p99 "
           f"{stats.percentile(xs, 99)}; final loss {obs['final_loss']}")
