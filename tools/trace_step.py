#!/usr/bin/env python
"""Trace one compiled train/decode step on the chip and print a per-op-category
device-time breakdown — the profiling companion to benchmarks/run_all.py for
deciding WHERE a step's time goes (MXU vs bandwidth vs op-dispatch tail).

    python -m tools.trace_step --what wrn          # WRN-16-8 train step
    python -m tools.trace_step --what gpt2_decode  # bs=1 int8 decode loop

Writes the raw Chrome trace under --out (default /tmp/tnn_trace) and prints
aggregated device-op totals. Uses jax.profiler (XPlane) — the same signal
xprof/tensorboard would show, reduced to a terminal table.
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import re


def aggregate(trace_dir: str, top: int = 30):
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz"))
    if not paths:
        raise SystemExit(f"no trace captured under {trace_dir} — the profiler "
                         "wrote nothing (is this backend supported?)")
    path = paths[-1]
    with gzip.open(path) as f:
        tr = json.load(f)
    pids = {e["pid"]: e["args"]["name"] for e in tr["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    evs = [e for e in tr["traceEvents"]
           if e.get("ph") == "X" and "TPU" in pids.get(e["pid"], "")]
    outer = [e for e in evs if e["name"].startswith(("jit_", "while"))]
    inner = [e for e in evs if not e["name"].startswith(("jit_", "while"))]
    total_outer = max((e["dur"] for e in outer), default=0)
    cat = collections.Counter()
    cnt = collections.Counter()
    for e in inner:
        base = re.sub(r"[.\d]+$", "", e["name"])
        cat[base] += e["dur"]
        cnt[base] += 1
    tot_inner = sum(cat.values())
    print(f"\nouter span {total_outer/1e3:.2f} ms; inner ops "
          f"{tot_inner/1e3:.2f} ms over {len(inner)} events "
          f"(gap/overhead {max(total_outer - tot_inner, 0)/1e3:.2f} ms)")
    print(f"{'ms':>9} {'count':>7}  op")
    for name, d in cat.most_common(top):
        print(f"{d/1e3:9.3f} {cnt[name]:7d}  {name}")
    return cat


def trace_wrn(out: str, batch: int = 256, steps: int = 3):
    import jax
    import jax.numpy as jnp

    from tnn_tpu import models, nn
    from tnn_tpu.train import create_train_state, make_train_step

    model = models.create("cifar100_wrn16_8")
    opt = nn.SGD(lr=0.1, momentum=0.9)
    state = create_train_state(model, opt, jax.random.PRNGKey(0),
                               (batch, 32, 32, 3))
    step = make_train_step(model, opt)
    x = jnp.zeros((batch, 32, 32, 3), jnp.bfloat16)
    y = jnp.zeros((batch,), jnp.int32)
    state, m = step(state, x, y)  # compile
    jax.block_until_ready(m["loss"])
    with jax.profiler.trace(out):
        for _ in range(steps):
            state, m = step(state, x, y)
        print("loss fetch:", float(m["loss"]))  # value fetch = sync


def trace_gpt2_train(out: str, batch: int = 8, seq: int = 512, steps: int = 2,
                     fused_head: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu import models, nn
    from tnn_tpu.train import create_train_state, make_train_step

    model = models.create("gpt2_small")
    opt = nn.AdamW(lr=1e-4)
    state = create_train_state(model, opt, jax.random.PRNGKey(0), (batch, seq))
    step = make_train_step(model, opt, compute_accuracy=not fused_head,
                           lm_head_chunk=8192 if fused_head else None)
    ids = jnp.asarray(np.arange(batch * seq, dtype=np.int32)
                      .reshape(batch, seq) % 50257)
    state, m = step(state, ids, ids)
    jax.block_until_ready(m["loss"])
    with jax.profiler.trace(out):
        for _ in range(steps):
            state, m = step(state, ids, ids)
        print("loss fetch:", float(m["loss"]))


def trace_gpt2_decode(out: str, new: int = 32):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu import models
    from tnn_tpu.models.gpt2 import generate
    from tnn_tpu.nn.quant import quantize_for_decode

    model = models.create("gpt2_small")
    v = model.init(jax.random.PRNGKey(0), (1, 8))
    params = jax.block_until_ready(quantize_for_decode(v["params"]))
    ids = jnp.asarray(np.arange(64, dtype=np.int32)[None] + 1)
    jax.block_until_ready(generate(model, params, ids, new))
    with jax.profiler.trace(out):
        toks = generate(model, params, ids, new)
        print("first tok:", int(np.asarray(toks)[0, 0]))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="wrn",
                    choices=["wrn", "gpt2_decode", "gpt2_train",
                             "gpt2_train_fused_head"])
    ap.add_argument("--out", default="/tmp/tnn_trace")
    ap.add_argument("--top", type=int, default=30)
    args = ap.parse_args(argv)
    if args.what == "wrn":
        trace_wrn(args.out)
    elif args.what == "gpt2_decode":
        trace_gpt2_decode(args.out)
    else:
        trace_gpt2_train(args.out,
                         fused_head=args.what.endswith("fused_head"))
    aggregate(args.out, args.top)


if __name__ == "__main__":
    main()
