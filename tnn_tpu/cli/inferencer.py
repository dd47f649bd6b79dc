#!/usr/bin/env python
"""Batch inference from a saved model (parity: examples/inferencer.cpp).

    python -m tnn_tpu.cli.inferencer --model-file model_snapshots/best/state.tnn \
        --dataset cifar100 --data-path data/cifar100

Reports accuracy + throughput over the eval split; --dataset synthetic runs on
fixed random data for smoke testing.
"""
import argparse
import time


import jax
import numpy as np

from tnn_tpu import checkpoint as ckpt_lib
from tnn_tpu import models
from tnn_tpu.data import factory
from tnn_tpu.data.loader import SyntheticDataLoader, prefetch
from tnn_tpu.train import make_predict


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model-file", required=True, help=".tnn model file")
    ap.add_argument("--dataset", default="synthetic")
    ap.add_argument("--data-path", default="data")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--num-classes", type=int, default=10)
    args = ap.parse_args(argv)

    if args.dataset == "synthetic":
        loader = SyntheticDataLoader(20 * args.batch_size, (32, 32, 3),
                                     args.num_classes)
    else:
        loader = factory.create(args.dataset, args.data_path, train=False)

    sample_shape = tuple(loader.data_shape)
    model, variables = ckpt_lib.load_model(
        args.model_file, input_shape=(args.batch_size,) + sample_shape)
    predict = make_predict(model)
    params, net_state = variables["params"], variables["state"]

    total, corrects, batches = 0, 0, 0
    t0 = time.perf_counter()
    for data, labels in prefetch(loader.batches(args.batch_size)):
        logits = predict(params, net_state, data)
        pred = np.asarray(logits.argmax(-1))
        corrects += int((pred == np.asarray(labels)).sum())
        total += len(labels)
        batches += 1
    dt = time.perf_counter() - t0
    print(f"accuracy {corrects / max(total, 1):.4f} over {total} samples, "
          f"{total / dt:.0f} samples/s")


cli = console_entry(main)


if __name__ == "__main__":
    main()
