#!/usr/bin/env python
"""Train a zoo model from config (parity: examples/trainer.cpp:16-80).

Config layering matches the reference: defaults <- .env / env vars <- --config
JSON <- CLI flags. Example:

    python -m tnn_tpu.cli.trainer --model cifar100_wrn16_8 --dataset cifar100 \
        --data-path data/cifar100 --epochs 20 --batch-size 256

With no dataset on disk, --dataset synthetic trains on fixed random data (useful
for smoke runs and benchmarks).
"""
import argparse


from tnn_tpu import models
from tnn_tpu.data import factory
from tnn_tpu.data.loader import SyntheticDataLoader
from tnn_tpu.train import train_model
from tnn_tpu.utils import compile_cache
from tnn_tpu.utils.config import TrainingConfig
from tnn_tpu.utils.env import load_env_file
from tnn_tpu.utils.hardware import device_line


def build_loaders(cfg: TrainingConfig, synthetic_classes: int):
    if cfg.dataset_name in ("", "synthetic"):
        shape = (32, 32, 3) if "mnist" not in cfg.model_name else (28, 28, 1)
        train = SyntheticDataLoader(50 * cfg.batch_size, shape, synthetic_classes,
                                    seed=cfg.seed)
        val = SyntheticDataLoader(10 * cfg.batch_size, shape, synthetic_classes,
                                  seed=cfg.seed + 1)
        return train, val
    train = factory.create(cfg.dataset_name, cfg.dataset_path, train=True,
                           seed=cfg.seed)
    try:
        val = factory.create(cfg.dataset_name, cfg.dataset_path, train=False)
    except (FileNotFoundError, OSError):
        val = None
    return train, val


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="", help="JSON config file")
    ap.add_argument("--model", default=None)
    ap.add_argument("--dataset", default=None,
                    help=f"one of {factory.available()} or 'synthetic'")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--resume", default=None, help="checkpoint dir to resume from")
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--profile", default=None, choices=["NONE", "NORMAL",
                                                        "CUMULATIVE"])
    ap.add_argument("--num-classes", type=int, default=10,
                    help="classes for synthetic data")
    ap.add_argument("--mesh", default=None,
                    help="parallel layout, e.g. data=2,pipe=4 or "
                         "data=2,model=2,seq=2 "
                         "(axes: data fsdp model pipe seq expert)")
    ap.add_argument("--num-microbatches", type=int, default=None,
                    help="pipeline microbatches per step (with --mesh pipe=N)")
    ap.add_argument("--pipeline-virtual", type=int, default=None,
                    help="interleaved virtual stages per pipe device (v>1 "
                         "splits the model into v*pp stages; bubble/v)")
    ap.add_argument("--seq-parallel-method", default=None,
                    choices=["ring", "ulysses"],
                    help="context-parallel scheme for --mesh seq=N")
    ap.add_argument("--history-out", default=None,
                    help="write the per-epoch metrics history (loss/accuracy "
                         "curves) to this JSON file — the committable "
                         "convergence artifact")
    args = ap.parse_args(argv)

    load_env_file()  # .env, as in the reference
    cfg = TrainingConfig().load_from_env()
    if args.config:
        cfg.load_from_json(args.config)
    for flag, field in [("model", "model_name"), ("dataset", "dataset_name"),
                        ("data_path", "dataset_path"), ("epochs", "epochs"),
                        ("batch_size", "batch_size"), ("resume", "resume"),
                        ("snapshot_dir", "snapshot_dir"),
                        ("profile", "profiler_type")]:
        v = getattr(args, flag)
        if v is not None:
            setattr(cfg, field, v)
    if args.lr is not None:
        cfg.optimizer = {**cfg.optimizer, "lr": args.lr}
    if args.mesh is not None:
        cfg.mesh_axes = {k: int(v) for k, v in
                         (kv.split("=") for kv in args.mesh.split(",") if kv)}
    if args.num_microbatches is not None:
        cfg.num_microbatches = args.num_microbatches
    if args.pipeline_virtual is not None:
        cfg.pipeline_virtual = args.pipeline_virtual
    if args.seq_parallel_method is not None:
        cfg.seq_parallel_method = args.seq_parallel_method

    if args.history_out:
        # fail on an unwritable path BEFORE the (possibly hours-long) run
        import os

        d = os.path.dirname(os.path.abspath(args.history_out))
        os.makedirs(d, exist_ok=True)
        open(args.history_out, "a").close()

    cache_dir = compile_cache.enable()
    # where this process really runs: a trainer that landed on the CPU must
    # not look like one that did not
    print(f"tnn-trainer: {device_line()} "
          f"compile_cache={compile_cache.describe(cache_dir)}")
    model = models.create(cfg.model_name)
    train_loader, val_loader = build_loaders(cfg, args.num_classes)
    state, history = train_model(model, cfg, train_loader, val_loader)
    if args.history_out:
        import json
        import platform as _platform

        import jax

        with open(args.history_out, "w") as f:
            json.dump({"model": cfg.model_name, "dataset": cfg.dataset_name,
                       "batch_size": cfg.batch_size, "epochs": cfg.epochs,
                       "device": str(jax.devices()[0]),
                       "host": _platform.platform(),
                       "history": history}, f, indent=2, default=float)
    final = history[-1] if history else {}
    print(f"done: {len(history)} epochs, final train loss "
          f"{final.get('train_loss', float('nan')):.4f}, "
          f"val acc {final.get('val_accuracy', 0.0):.4f}")
    return state, history


cli = console_entry(main)


if __name__ == "__main__":
    main()
