"""Headline benchmark: WRN-16-8 CIFAR-100 training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
One process, on the chip or not at all: with no TPU it exits non-zero and
prints no value.

Baseline (BASELINE.md): the reference's flagship run is CIFAR-100 WRN-16-8 at
~102-110 ms/batch for bs=256 over a 2-machine RoCE pipeline => ~2.4k img/s
(sample_logs/cifar100_wrn16_8:348-368). vs_baseline = our img/s per chip / 2400.

Timing uses benchmarks/common.py:time_loop (difference-of-two-runs) behind a
known-FLOP matmul self-check. The wider harness is benchmarks/run_all.py.
"""
import json
import sys
import time

BATCH = 256
BASELINE_IMG_S = 2400.0
WARMUP_STEPS = 8
MEASURE_STEPS = 100
METRIC = "wrn16_8_cifar100_train_img_per_sec_per_chip"


def measure():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.utils import compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    compile_cache.enable()

    from benchmarks.common import sync, time_loop, timing_selfcheck
    from tnn_tpu import models, nn
    from tnn_tpu.train import create_train_state, make_train_step

    selfcheck_mfu = timing_selfcheck()
    rng = jax.random.PRNGKey(0)
    model = models.create("cifar100_wrn16_8")  # bf16 compute, f32 master params
    opt = nn.SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    sched = nn.WarmupCosineAnnealing(warmup=200, t_max=20000)
    state = create_train_state(model, opt, rng, (BATCH, 32, 32, 3))
    step = make_train_step(model, opt, scheduler=sched)

    rs = np.random.RandomState(0)
    data = jnp.asarray(rs.randn(BATCH, 32, 32, 3), jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, 100, BATCH), jnp.int32)

    for _ in range(WARMUP_STEPS):
        state, m = step(state, data, labels)
    sync(m["loss"])
    holder = {"s": state}

    def run(n):
        t0 = time.perf_counter()
        m = None
        for _ in range(n):
            holder["s"], m = step(holder["s"], data, labels)
        sync(m["loss"])
        return time.perf_counter() - t0

    dt = time_loop(run, MEASURE_STEPS, min_delta=0.35, pairs=3)
    img_s = BATCH / dt
    print(json.dumps({
        "metric": METRIC,
        "value": round(img_s, 1),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "timing_selfcheck_mfu": round(selfcheck_mfu, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(measure())
