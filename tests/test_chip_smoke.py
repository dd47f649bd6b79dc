"""chip_smoke.py's verdict, the compile-cache placement rule, and the
one-process-per-chip import rule — everything about the chip contract that a
CPU can check."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from tnn_tpu.serving import (EngineSupervisor, FaultPlan, InferenceEngine,
                             compile_cache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = dict(num_blocks=32, block_size=4, max_batch_size=4, max_seq_len=32)


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    return model, params


def _serve(model, params, faults=None):
    """One supervised engine run; returns what the smoke's verdict reads:
    the requests by id, the event stream, the summary."""
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 128, 8).tolist()
    prompts = [rng.integers(0, 128, n).tolist() for n in (5, 9)] \
        + [prefix + rng.integers(0, 128, 3).tolist() for _ in range(2)]
    eng = InferenceEngine(model, params, **KW, faults=faults)
    sup = EngineSupervisor(eng, max_restarts=3, restart_backoff_s=0.0)
    events = []
    sup.event_sink = events.append
    by_id = {}
    for p in prompts:
        rid = sup.submit(np.asarray(p, np.int32), 6)
        by_id[rid] = {"tokens": p, "max_new_tokens": 6}
        sup.run_sync()      # serially, so the last prompt finds the prefix
    return by_id, events, sup.stats()


class TestVerdict:
    def test_clean_run_passes(self, tiny_lm):
        by_id, events, summary = _serve(*tiny_lm)
        assert chip_smoke.check_serve(by_id, events, summary, 128) == []

    def test_caught_crash_fails_the_smoke(self, tiny_lm):
        """The engine survives an injected crash — every request still ends
        ``done`` and a server would exit 0 — and the smoke must not."""
        by_id, events, summary = _serve(
            *tiny_lm, faults=FaultPlan(step_crash_calls=(3,)))
        assert all(e["event"] in ("token", "done") for e in events)
        failures = chip_smoke.check_serve(by_id, events, summary, 128)
        assert any("engine_restarts" in f for f in failures), failures

    def test_failed_request_and_bad_tokens_fail_the_smoke(self):
        by_id = {0: {"tokens": [1, 2], "max_new_tokens": 2},
                 1: {"tokens": [3], "max_new_tokens": 2}}
        events = [{"event": "error", "id": 0, "reason": "decode step failed"},
                  {"event": "done", "id": 1, "tokens": [5, 999],
                   "finish_reason": "length"}]
        summary = {"failed": 1, "engine_restarts": 0, "step_retries": 0,
                   "prefill_tokens_saved": 0}
        failures = "\n".join(
            chip_smoke.check_serve(by_id, events, summary, 128))
        for needle in ("request 0", "outside the vocabulary",
                       "failed = 1", "prefill_tokens_saved"):
            assert needle in failures

    def test_default_invocation_refuses_a_cpu(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 2
        assert out.stdout == "" and "no TPU" in out.stderr


class TestCompileCacheRule:
    @pytest.fixture(autouse=True)
    def _restore(self):
        yield
        compile_cache.disable()

    def test_env_set_means_code_sets_no_directory(self, monkeypatch,
                                                  tmp_path):
        compile_cache.disable()
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "outside"))
        got = compile_cache.enable(str(tmp_path / "flag"))
        assert got == str(tmp_path / "outside")   # --compile-cache ignored
        assert jax.config.jax_compilation_cache_dir is None
        assert not (tmp_path / "flag").exists()

    def test_unset_means_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.default_dir() == want
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        # the default stays off on the CPU backend (see the module docstring)
        assert compile_cache.enable() is None
        made = os.path.isdir(want)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        try:
            assert compile_cache.enable() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            compile_cache.disable()
            if not made:
                os.rmdir(want)


def test_importing_the_package_touches_no_device():
    """A process that imports the serving stack must not take the chip: the
    one that runs it may be a child."""
    code = ("import tnn_tpu, tnn_tpu.serving, tnn_tpu.cli.serve, "
            "tnn_tpu.cli.trainer, chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=dict(os.environ, PYTHONPATH=REPO),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


def test_stdin_lines_sent_together_are_all_read():
    """Two requests in one write must both come out of the first read: a
    buffered readline() would strand the second until the client wrote
    again (which is how the smoke's client found it)."""
    from tnn_tpu.cli.serve import _read_stdin_lines

    r, w = os.pipe()
    try:
        os.write(w, b'{"id": 1}\n{"id": 2}\n{"id"')
        lines, pending, eof = _read_stdin_lines(r, b"", 1.0)
        assert lines == [b'{"id": 1}', b'{"id": 2}'] and not eof
        assert _read_stdin_lines(r, pending, 0.0) == ([], pending, False)
        os.write(w, b": 3}")
        os.close(w)
        w = None
        lines, pending, eof = _read_stdin_lines(r, pending, 1.0)
        assert lines == [] and pending == b'{"id": 3}' and not eof
        assert _read_stdin_lines(r, pending, 1.0) == ([b'{"id": 3}'], b"",
                                                      True)
    finally:
        os.close(r)
        if w is not None:
            os.close(w)


@pytest.mark.tp
def test_engine_device_places_params_pool_and_steps(tiny_lm, tp, monkeypatch):
    """``device=`` is how a fleet puts replica i on chip i: everything the
    engine owns lives there, no step hops devices (the transfer guard would
    raise), and tokens match the default-device engine."""
    monkeypatch.setenv("TNN_DEBUG_SYNC", "1")
    model, params = tiny_lm
    prompts = [np.arange(3, 3 + n, dtype=np.int32) for n in (5, 9)]

    def run(**kw):
        eng = InferenceEngine(model, params, **KW, overlap=True, **kw)
        rids = [eng.submit(p, 5) for p in prompts]
        out = eng.run_until_complete()
        return eng, [out[r] for r in rids]

    dev = jax.devices()[1]
    _, base = run()
    eng, placed = run(device=dev)
    assert placed == base and eng.stats()["failed"] == 0
    assert eng.pool.pages_k.sharding.device_set == {dev}
    assert all(x.sharding.device_set == {dev}
               for x in jax.tree_util.tree_leaves(eng.params))
    with pytest.raises(ValueError, match="mesh"):
        InferenceEngine(model, params, **KW, tp=tp, device=dev)
