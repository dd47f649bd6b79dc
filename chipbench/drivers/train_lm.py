"""Driver ``train_lm``: the body of ``tnn-train-gpt2``
(``tnn_tpu/cli/train_gpt2.py:main``) around the same calls, line for line:
``GPT2(dropout=0.0, ...)``, ``AdamW(lr, weight_decay 0.01, clip 1.0)``,
``WarmupCosineAnnealing``, ``create_train_state``,
``make_train_step(steps_per_call=1)``, ``TokenStreamDataLoader.random_windows``
over a token file, a loss fetch every 20 steps. The CLI itself times
compilation into one wall clock and has no steady window, which is why its
body is mirrored here and not called (the missing hook is listed in PERF.md).

Set-up builds ONE compiled step with its state, drives it through its first
three steps with the window's own ``one_step`` (those are what ``correct``
follows), and hands the same object to the window. The weights and the token
file are made here from the seed; the program takes them in place of its own
initialisation.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

from chipbench import spec

CHECKED_STEPS = 3
SAMPLE_ROWS = 8


def grad_sample(tree):
    """A sample of a gradient-shaped tree small enough to keep on the host:
    every vector whole, the first ``SAMPLE_ROWS`` rows of every matrix."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: x[:SAMPLE_ROWS] if x.ndim == 2 else x, tree)


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu import nn
    from tnn_tpu.data.token_stream import TokenStreamDataLoader
    from tnn_tpu.models.gpt2 import GPT2
    from tnn_tpu.train import create_train_state, make_train_step
    from tnn_tpu.utils import compile_cache

    config = ctx.config
    part = config["rehearsal"] if ctx.rehearse else config
    tr = part["train"]
    reference = spec.plugin("reference", config["reference"])
    sz = ctx.sizes = reference.sizes_of(part)
    batch, seq = tr["batch"], tr["seq"]

    compile_cache.enable()
    params0 = reference.make_params(sz, ctx.seed)
    workdir = tempfile.mkdtemp(prefix="chipbench-train-")
    try:
        rng = np.random.default_rng([ctx.seed % (2 ** 63), 4])
        path = os.path.join(workdir, "train.bin")
        rng.integers(0, sz["vocab_size"], tr["token_file_tokens"],
                     dtype=np.uint16).tofile(path)
        loader = TokenStreamDataLoader(path, seq)

        model = GPT2(dropout=0.0, vocab_size=sz["vocab_size"], max_len=seq,
                     num_layers=sz["n_layer"], d_model=sz["n_embd"],
                     num_heads=sz["n_head"], backend=tr["backend"],
                     num_kv_heads=None)
        model.init = lambda *a, **k: {"params": params0, "state": {}}
        opt = nn.AdamW(lr=tr["lr"], weight_decay=tr["weight_decay"],
                       grad_clip_norm=tr["grad_clip_norm"])
        total = tr["horizon_steps"]
        warmup = max(10, total // 20)
        sched = nn.WarmupCosineAnnealing(warmup=warmup, t_max=total)
        state = create_train_state(model, opt, jax.random.PRNGKey(0),
                                   (batch, seq))
        step = make_train_step(model, opt, scheduler=sched,
                               compute_accuracy=True, lm_head_chunk=None,
                               steps_per_call=1)
        del params0         # the state holds them now

        spans = {"input": []}
        last = {}           # the newest step's loss, still on the device
        fed = []            # the first steps' batches, for the reference

        def one_step(state, c, keep=False):
            """One trip of the CLI's loop: windows from the host loader,
            transfer, dispatch; a loss fetch every ``loss_fetch_every``."""
            t0 = time.perf_counter()
            data, labels = loader.random_windows(batch, rng)
            d = jnp.asarray(data, jnp.int32)
            lab = jnp.asarray(labels, jnp.int32)
            spans["input"].append(time.perf_counter() - t0)
            if keep:
                fed.append((np.array(data, np.int32),
                            np.array(labels, np.int32)))
            state, m = step(state, d, lab)
            last["loss"] = m["loss"]
            loss = None
            if keep or c % tr["loss_fetch_every"] == 0:
                loss = float(m["loss"])
            return state, loss

        # -- the first steps, through the window's own call and feed --------
        t0 = time.perf_counter()
        got = {"loss": []}
        norms = jax.jit(_leaf_norms)
        for c in range(CHECKED_STEPS):
            state, loss = one_step(state, c, keep=True)
            got["loss"].append(loss)
            if c == 0:      # Adam's first moment after one step: (1-b1) g
                got["grad_norms"] = jax.tree_util.tree_map(
                    lambda x: float(x) / (1.0 - 0.9),
                    norms(state.opt_state["m"]))
                got["grad_sample"] = jax.tree_util.tree_map(
                    lambda x: np.asarray(x, np.float32) / (1.0 - 0.9),
                    jax.jit(grad_sample)(state.opt_state["m"]))
        p0 = reference.make_params(sz, ctx.seed)
        got["delta_norms"] = jax.tree_util.tree_map(
            float, jax.jit(lambda a, b: _leaf_norms(
                jax.tree_util.tree_map(jnp.subtract, a, b)))(state.params, p0))
        del p0
        jax.block_until_ready(state)
        ctx.note(f"first {CHECKED_STEPS} steps (compile or cache load "
                 f"included): {time.perf_counter() - t0:.1f} s; losses "
                 f"{got['loss']}")
        # a few more, unfetched, so that the window opens on a busy queue
        c = CHECKED_STEPS
        for _ in range(tr["warm_steps"]):
            state, _ = one_step(state, c)
            c += 1
        jax.block_until_ready(state)
        spans["input"].clear()

        # -- the window: the mix's generator says what runs when ------------
        class Job:
            """What a generator drives: one trip of the loop, and a wait for
            everything dispatched."""
            steps = nonfinite = 0

            def step(self):
                nonlocal state, c
                state, loss = one_step(state, c)
                if loss is not None and not np.isfinite(loss):
                    self.nonfinite += 1
                c += 1
                self.steps += 1

            def sync(self):
                jax.block_until_ready(state)

        job = Job()
        spec.plugin("generators", ctx.traffic["generator"]).drive(
            job, ctx.traffic, ctx)
        n, bad = job.steps, job.nonfinite
        final_loss = float(last["loss"])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    del state, step, loader
    return {"kind": "train", "steps_in_window": n, "batch": batch, "seq": seq,
            "spans": spans, "got": got, "fed": fed, "sizes": sz,
            "reference": reference, "nonfinite": bad,
            "final_loss": final_loss,
            "schedule": {"warmup": warmup, "t_max": total}}
