#!/usr/bin/env python
"""Control-plane worker: trains per the deployed config (parity: examples/tcp_worker.cpp).

    python -m tnn_tpu.cli.dist_worker --coordinator host:5555 [--rank 0]

Receives a TrainingConfig dict from the coordinator, runs train_model between the
"start" and "done" barriers, and answers profiling/save/health RPCs from the
background event loop. For real multi-host data parallelism, also set
config["jax_coordinator"] so each worker calls jax.distributed.initialize and the
train step's collectives span hosts.
"""
import argparse
import os

from tnn_tpu.distributed import Worker


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", required=True, help="host:port")
    ap.add_argument("--rank", type=int, default=None)
    args = ap.parse_args(argv)
    host, port = args.coordinator.rsplit(":", 1)

    w = Worker(host, int(port), rank=args.rank).start()
    print(f"joined as rank {w.rank}/{w.world}")

    # register on_save FIRST THING — a SAVE_TO_FILE RPC can arrive any time
    # after the handshake, including while this process is still importing jax
    # or building the model. The train step donates its TrainState (buffers of
    # a stored state are deleted by the NEXT step), so the event-loop thread
    # cannot save a kept reference; instead it queues a request that the
    # training thread services synchronously at its next state_hook firing,
    # while the state is still alive.
    import threading

    pending = []
    pending_lock = threading.Lock()
    final = {}
    model_ref = {}

    def _save_to(path, st):
        from tnn_tpu.checkpoint import Checkpoint

        # rank-qualified: on a shared filesystem, all ranks saving the same
        # step to the same directory would race on state.tnn and _gc
        Checkpoint(os.path.join(path, f"rank{w.rank}")).save(
            st, model=model_ref.get("model"))

    def state_hook(st):
        with pending_lock:
            reqs, pending[:] = pending[:], []
        for req in reqs:
            try:
                _save_to(req["path"], st)
            except Exception as e:
                req["err"] = str(e)
            req["done"].set()

    def on_save(path):
        # the final-state check and the request append are atomic with the
        # set-final-then-drain sequence below (same lock), so a request can
        # never be stranded between "training ended" and "drain ran"
        with pending_lock:
            st = final.get("state")
            if st is None:
                req = {"path": path, "done": threading.Event(), "err": None}
                pending.append(req)
        if st is not None:  # training over: the final state is not donated
            _save_to(path, st)
            return
        # generous wait: the first state only exists once training starts, and
        # hook firings can be minutes apart around epoch-end validation; the
        # worker event loop is NOT blocked meanwhile (the Worker services
        # SAVE_TO_FILE on its own thread)
        if not req["done"].wait(timeout=600):
            raise RuntimeError("save not serviced within 600s "
                               "(training thread stalled?)")
        if req["err"]:
            raise RuntimeError(req["err"])

    w.on_save = on_save

    # config arrives via the event loop; wait for it
    import time
    while w.config is None and w.running:
        time.sleep(0.05)
    config = dict(w.config or {})
    per_rank = (config.pop("ranks", {}) or {}).get(str(w.rank), {})
    config.update(per_rank)

    if "jax_coordinator" in config:  # multi-host XLA data plane
        import jax

        jax.distributed.initialize(config["jax_coordinator"],
                                   num_processes=w.world, process_id=w.rank)

    from tnn_tpu import models
    from tnn_tpu.data.loader import SyntheticDataLoader
    from tnn_tpu.train import train_model
    from tnn_tpu.utils.config import TrainingConfig

    known = set(TrainingConfig.__dataclass_fields__)
    cfg = TrainingConfig().update({k: v for k, v in config.items() if k in known})
    model = models.create(cfg.model_name)
    if cfg.dataset_name in ("", "synthetic"):
        shape = (28, 28, 1) if "mnist" in cfg.model_name else (32, 32, 3)
        loader = SyntheticDataLoader(20 * cfg.batch_size, shape,
                                     100 if "100" in cfg.model_name else 10,
                                     seed=cfg.seed + w.rank)
    else:
        from tnn_tpu.data import factory

        loader = factory.create(cfg.dataset_name, cfg.dataset_path, train=True)

    model_ref["model"] = model

    w.barrier("start", timeout=600)
    state, history = train_model(model, cfg, loader, state_hook=state_hook)
    with pending_lock:
        final["state"] = state
    state_hook(state)  # drain requests that raced with training completion
    print(f"rank {w.rank}: trained {len(history)} epochs, "
          f"final loss {history[-1]['train_loss']:.4f}")
    w.barrier("done", timeout=600)
    w.join(timeout=60)


cli = console_entry(main)


if __name__ == "__main__":
    main()
