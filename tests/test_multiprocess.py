"""REAL multi-process integration tests: N OS processes running
examples/dist_worker.py against an in-process Coordinator.

The in-thread tests (test_distributed.py) prove protocol logic; these prove the
control plane composes with actual worker processes doing actual training —
the analog of the reference's docker-compose multi-node runs (sample_logs/),
which it only ever ran manually. Workers run with JAX_PLATFORMS=cpu (a
subprocess must never take the chip from under the test process).
"""
import os
import signal
import subprocess
import sys
import tempfile

import pytest

from tnn_tpu.checkpoint import Checkpoint
from tnn_tpu.distributed import Coordinator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_worker(port: int, rank=None, log=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # Sanitizer lanes (scripts/ci.sh --sanitize) LD_PRELOAD lib{a,t}san into
    # pytest. Do NOT propagate that into worker subprocesses: ASan's
    # __cxa_throw interceptor hard-aborts ("real___cxa_throw != 0" CHECK)
    # when jaxlib's bundled MLIR bindings throw C++ exceptions during the
    # worker's jit compile — an ASan-runtime/jaxlib incompatibility, nothing
    # of ours. The parent keeps full instrumentation (coordinator side of the
    # native control plane + decoders); workers run the release lib.
    preload = env.get("LD_PRELOAD", "")
    if "asan" in preload or "tsan" in preload:
        env.pop("LD_PRELOAD", None)
        env.pop("TNN_NATIVE_LIB", None)  # sanitized .so needs the preload
    # -m with cwd=REPO resolves tnn_tpu from the clone even when the package
    # is not pip-installed (a bare `python examples/dist_worker.py` would not)
    cmd = [sys.executable, "-m", "tnn_tpu.cli.dist_worker",
           "--coordinator", f"127.0.0.1:{port}"]
    if rank is not None:
        cmd += ["--rank", str(rank)]
    return subprocess.Popen(cmd, env=env, cwd=REPO, stdout=log or subprocess.DEVNULL,
                            stderr=subprocess.STDOUT)


def _base_config(tmp: str):
    return {
        "epochs": 1, "batch_size": 16, "max_steps": 5, "model_name": "mnist_cnn",
        "dataset_name": "synthetic", "snapshot_dir": os.path.join(tmp, "snaps"),
        "progress_print_interval": 1, "profiler_type": "NORMAL",
    }


def _cleanup(procs, coord):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
    coord.close()


class TestMultiProcess:
    def test_dp_run_profiles_and_save(self, tmp_path):
        """Two worker PROCESSES train to completion; profiles merge across
        process boundaries; a mid-run save RPC lands from every rank."""
        tmp = str(tmp_path)
        coord = Coordinator(num_workers=2)
        procs = [_spawn_worker(coord.port()), _spawn_worker(coord.port())]
        try:
            ranks = coord.wait_for_workers(timeout=90)
            assert ranks == [0, 1]
            coord.start_profiling()
            coord.deploy_config(_base_config(tmp), timeout=300)
            coord.barrier("start", timeout=300)  # jax import + compile
            # mid-run save: must succeed while training is in flight
            coord.save_all(os.path.join(tmp, "mid"), timeout=300)
            for r in (0, 1):
                assert Checkpoint(
                    os.path.join(tmp, "mid", f"rank{r}")).latest_path(), \
                    f"rank {r} did not save"
            coord.barrier("done", timeout=300)
            merged = coord.collect_profiles(timeout=120)
            sources = {e.source for e in merged.events}
            assert {"worker0", "worker1"} <= sources, sources
            coord.shutdown(timeout=30)
            for p in procs:
                assert p.wait(timeout=60) == 0
        finally:
            _cleanup(procs, coord)

    def test_worker_death_detected_and_rank_rejoins(self, tmp_path):
        """SIGKILL one worker process mid-run: the coordinator detects it via
        disconnect, and a fresh process re-admits the dead rank (the
        reference's recovery commands are unimplemented stubs,
        worker.hpp:216-277)."""
        tmp = str(tmp_path)
        coord = Coordinator(num_workers=2, heartbeat_timeout=600)
        procs = [_spawn_worker(coord.port(), rank=0),
                 _spawn_worker(coord.port(), rank=1)]
        try:
            coord.wait_for_workers(timeout=90)
            cfg = dict(_base_config(tmp), epochs=50, max_steps=-1)
            # config ack + barrier deadlines are generous because a fresh
            # process pays a full jax import, and on a 1-CPU host under
            # concurrent suite load that alone has exceeded two minutes
            coord.deploy_config(cfg, timeout=300)
            coord.barrier("start", timeout=300)
            procs[0].send_signal(signal.SIGKILL)  # hard crash, no goodbye
            # event-driven: the kernel's RST on the dead pipe wakes the wait
            coord.wait_failed(0, timeout=120)
            # restart rank 0 in a new process: rejoin path (woken by the
            # rejoin HANDSHAKE, not a polling lap)
            procs.append(_spawn_worker(coord.port(), rank=0))
            coord.wait_alive(0, timeout=300)
        finally:
            _cleanup(procs, coord)
