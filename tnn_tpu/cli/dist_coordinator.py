#!/usr/bin/env python
"""Control-plane coordinator for multi-host runs (parity: examples/tcp_coordinator.cpp).

    python -m tnn_tpu.cli.dist_coordinator --num-workers 2 --port 5555 \
        --config '{"model_name": "cifar100_wrn16_8", "epochs": 5}'

Waits for workers, deploys the config, releases the "start" barrier, then
collects merged profiles and shuts everyone down when workers hit the "done"
barrier. The tensor traffic itself rides XLA collectives (jax.distributed);
this process only orchestrates.
"""
import argparse
import json


from tnn_tpu.distributed import Coordinator


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-workers", type=int, required=True)
    ap.add_argument("--port", type=int, default=5555)
    ap.add_argument("--bind", default="")
    ap.add_argument("--config", default="{}",
                    help="JSON string or @file.json to deploy to workers")
    ap.add_argument("--profile-out", default="",
                    help="write merged Chrome trace here at the end")
    args = ap.parse_args(argv)

    cfg = args.config
    if cfg.startswith("@"):
        with open(cfg[1:]) as f:
            cfg = f.read()
    config = json.loads(cfg)

    def on_failure(rank):
        print(f"WORKER {rank} FAILED — remaining workers keep running; restart "
              f"it with --rank {rank} to rejoin (the coordinator re-admits a "
              f"failed rank's handshake)")

    with Coordinator(args.num_workers, bind=args.bind, port=args.port,
                     on_failure=on_failure) as coord:
        print(f"coordinator listening on port {coord.port()}")
        ranks = coord.wait_for_workers(timeout=600)
        print(f"workers joined: {ranks}")
        coord.deploy_config(config)
        coord.start_profiling()
        coord.barrier("start", timeout=600)
        print("training started; waiting for done barrier")
        coord.barrier("done", timeout=24 * 3600)
        prof = coord.collect_profiles()
        if args.profile_out:
            prof.to_chrome_trace(args.profile_out)
            print(f"merged profile -> {args.profile_out}")
        coord.shutdown()
        print("all workers shut down")


cli = console_entry(main)


if __name__ == "__main__":
    main()
