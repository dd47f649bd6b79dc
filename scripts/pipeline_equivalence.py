#!/usr/bin/env python
"""WRN-16-8 pipeline-vs-single-device equivalence artifact generator.

Trains the FULL cifar100_wrn16_8 (~11M params) for a few steps through the
compiled heterogeneous pipeline and through single-device gradient
accumulation from the SAME init, and writes per-step relative loss diffs to
logs/ (``--out`` names another file). This is the functional-correctness
evidence behind the
flagship pipeline (round-3 artifact: rel_diff <= 6e-5 at v=1); --virtual 2
exercises the interleaved schedule on the same model (round-4, VERDICT #3).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/pipeline_equivalence.py --virtual 2 --steps 3

Runs anywhere: on the virtual 8-device CPU mesh (numerics are
platform-independent at f32) and on the chip. The records of earlier rounds
that lay under benchmarks/results/ went with PR 48 (no test read them).
"""
import argparse
import json
import os
import sys
import time

# On the virtual CPU mesh a heavy stage can hold one emulated device at a
# ppermute long enough to trip XLA's 20s/40s collective rendezvous watchdog
# (the host may have ONE core running all 8 device threads); raise it before
# jax loads. Harmless on real TPU.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=600"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=3600")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual", type=int, default=1)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--num-mb", type=int, default=4)
    ap.add_argument("--mb", type=int, default=8, help="microbatch size")
    ap.add_argument("--steps", type=int, default=3)
    # Tolerance is dtype-aware (None -> 1e-4 f32, 2e-3 bf16). Justification:
    # at f32 the pipeline and the grad-accum reference are bit-identical
    # (committed artifact: rel_diff 0.0 at every step), so the schedule itself
    # is exact. Under bf16 compute the two paths sum microbatch partials in
    # different orders through activations with 8-bit mantissas; one rounding
    # step is up to 2^-9 ~= 2e-3 relative, and after one SGD update the drift
    # feeds back through the weights. 2e-3 (one bf16 ulp of headroom) is the
    # tight bound that is still schedule-independent; the observed bf16 diff
    # is ~1.8e-4, an order of magnitude inside it. A genuine schedule bug
    # (dropped microbatch, stale weights) shifts the loss by >1e-2 at these
    # scales, so the gate still catches real failures.
    ap.add_argument("--tol", type=float, default=None)
    ap.add_argument("--f32", action="store_true",
                    help="f32 compute: isolates schedule exactness from bf16 "
                         "reduction-order noise (step>=1 under bf16 compounds "
                         "one optimizer update's worth of rounding drift)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.tol is None:
        args.tol = 1e-4 if args.f32 else 2e-3

    from tnn_tpu import models, nn, parallel
    from tnn_tpu.train import make_train_step
    from tnn_tpu.train.step import create_train_state

    v, pp, num_mb, mb = args.virtual, args.pp, args.num_mb, args.mb
    B = num_mb * mb
    mesh = parallel.make_mesh(pipe=pp)
    policy = None
    if args.f32:
        from tnn_tpu.core import dtypes as dt

        policy = dt.FP32
    model = models.create("cifar100_wrn16_8", policy=policy)
    parts = parallel.partitioner.balanced_partitions(model, v * pp,
                                                     (mb, 32, 32, 3))
    stages = parallel.partitioner.split(model, parts)
    opt = nn.SGD(lr=0.1, momentum=0.9)
    in_dt = jnp.float32 if args.f32 else jnp.bfloat16
    pipe, step_fn, init_fn = parallel.make_pipeline_train_step(
        stages, opt, mesh, (mb, 32, 32, 3), num_microbatches=num_mb,
        virtual=v, input_dtype=in_dt)
    pstate = init_fn(jax.random.PRNGKey(0))

    # single-device reference from the pipeline's exact init
    ref_opt = nn.SGD(lr=0.1, momentum=0.9)
    rstate = create_train_state(model, ref_opt, jax.random.PRNGKey(0),
                                (B, 32, 32, 3))
    stage_vars = pipe.unpack_stage_variables(pstate.params, pstate.net_state)
    ref_params, ref_net = dict(rstate.params), dict(rstate.net_state)

    def global_key(part, local_key):
        # stage-local child key "01_batchnorm" -> unsplit key "04_batchnorm"
        j, typ = int(local_key.split("_")[0]), local_key.split("_", 1)[1]
        return f"{part.start + j:02d}_{typ}"

    for part, sv in zip(parts, stage_vars):
        for lk, val in sv["params"].items():
            ref_params[global_key(part, lk)] = val
        for lk, val in sv["state"].items():
            ref_net[global_key(part, lk)] = val
    rstate = rstate._replace(params=ref_params, net_state=ref_net,
                             opt_state=ref_opt.init(ref_params))
    ref_step = make_train_step(model, ref_opt, grad_accum=num_mb,
                               donate=False)

    rs = np.random.RandomState(0)
    rows, worst = [], 0.0
    for step in range(args.steps):
        data = jnp.asarray(rs.randn(B, 32, 32, 3), in_dt)
        labels = jnp.asarray(rs.randint(0, 100, B), jnp.int32)
        t0 = time.time()
        pstate, pm = step_fn(pstate, data, labels)
        rstate, rm = ref_step(rstate, data, labels)
        pl, rl = float(pm["loss"]), float(rm["loss"])
        rel = abs(pl - rl) / max(abs(rl), 1e-9)
        worst = max(worst, rel)
        rows.append({"step": step, "pipeline_loss": round(pl, 6),
                     "single_device_loss": round(rl, 6),
                     "rel_diff": round(rel, 8)})
        print(f"step {step}: pipe {pl:.6f} ref {rl:.6f} rel {rel:.2e} "
              f"({time.time()-t0:.1f}s)")

    layout = f"pp={pp}, num_microbatches={num_mb}, virtual={v}"
    out = {
        "metric": "wrn16_8_cifar100_pipeline_equivalence",
        "model": "cifar100_wrn16_8 (full, ~11M params)",
        "layout": layout + f", {jax.device_count()}-device "
                  f"{jax.devices()[0].platform} mesh",
        "schedule": "interleaved" if v > 1 else "gpipe",
        "compute": "f32" if args.f32 else "bf16",
        "ideal_bubble_fraction": round((pp - 1) / v / (num_mb + (pp - 1) / v), 4),
        "stage_layers": [len(s.children) for s in stages],
        "steps": rows,
        "max_rel_diff": worst,
        "tol": args.tol,
        "tol_rationale": ("f32: schedule is bit-exact (observed 0.0)" if args.f32
                          else "bf16: one 8-bit-mantissa rounding is 2^-9~=2e-3 "
                               "relative; reduction order differs between the "
                               "pipeline and grad-accum paths, so diffs up to "
                               "one bf16 ulp are numerics, not schedule bugs"),
        "pass": worst <= args.tol,
        "unix_time": time.time(),
    }
    path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "logs",
        f"wrn16_8_pipeline_equivalence_v{v}_pp{pp}"
        + ("_f32" if args.f32 else "") + ".json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}; max rel diff {worst:.2e} "
          f"({'PASS' if out['pass'] else 'FAIL'} at tol {args.tol})")
    return 0 if out["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
