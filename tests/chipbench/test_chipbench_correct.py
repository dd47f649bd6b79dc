"""``correct`` shown to fail: the rest of a run, past the harness's look for
a chip (``--rehearse``: tiny sizes, CPU), with the timed path sound, with it
broken underneath, and with the int8 control in the program's place."""
import pytest

from chipbench import control
from chipbench import run as bench_run


def _run(workload, seed, seconds, **overrides):
    args = bench_run.parse(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", "0", "--rehearse"])
    vars(args).update(overrides)
    return bench_run.run_cell(args)


@pytest.fixture(scope="module")
def served():
    return _run("gpt2-large.decode", 2 ** 31 + 77, 3)


@pytest.fixture(scope="module")
def trained():
    return _run("gpt2-medium.train", 2 ** 31 + 78, 2)


def test_sound_serving_run_is_correct_and_a_rehearsal_has_no_metric(served):
    result, obs = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert obs["readings"]["tokens"] >= 50


def _limits(obs):
    return obs["ctx"].config["rehearsal"]["limits"]


def test_lower_precision_control_fails_the_serving_comparison(served):
    _, obs = served
    limits = _limits(obs)
    low = control.control_readings(obs)
    assert low["gap_max"] > limits["gap_max"] \
        or low["gap_mean"] > limits["gap_mean"], (low, limits)
    assert low["gap_mean"] > 3 * obs["readings"]["gap_mean"]


def test_an_altered_token_makes_a_serving_run_incorrect(monkeypatch):
    from tnn_tpu.serving.supervisor import EngineSupervisor

    real = EngineSupervisor._emit

    def emit(self, rid, ev):
        if ev.get("event") == "token":      # altered where it is produced
            ev = dict(ev, token=(int(ev["token"]) + 1) % 50257)
        return real(self, rid, ev)

    monkeypatch.setattr(EngineSupervisor, "_emit", emit)
    result, obs = _run("gpt2-large.decode", 2 ** 31 + 79, 3)
    assert result["correct"] is False
    assert obs["readings"]["gap_max"] > _limits(obs)["gap_max"]


def test_the_open_loop_mix_becomes_a_cell_by_entries_alone(monkeypatch):
    """The chat mix is not a cell yet (PERF.md section 7). Its files are
    there: entries in BENCHMARK.json make it one."""
    from chipbench import spec
    from chipbench.end_to_end import itl_p95_ms, ttft_p90_ms

    bench = spec.benchmark()
    cell = "gpt2-large.chat"
    bench["workloads"].append({"name": cell, "config": "gpt2-large-serve",
                               "traffic": "chat", "chips": 1, "why": "-"})
    bench["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": [cell]}
        for n in ("ttft_p90_ms", "itl_p95_ms")]
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    result, obs = _run(cell, 2 ** 31 + 83, 4,
                       traffic_override={"rate_per_s": 4.0, "grace_s": 30.0})
    assert result["correct"] is True and result["failed"] == 0
    measured = [r for r in obs["client"].reqs.values() if r.measured]
    assert result["attempted"] == len(measured) >= 8
    assert all(0 <= r.due - obs["client"].t_open < 4 for r in measured)
    assert any(not r.measured and r.id.startswith("r")      # the lead-in
               for r in obs["client"].reqs.values())
    assert ttft_p90_ms.value(obs) > 0 and itl_p95_ms.value(obs) > 0


def test_sound_training_run_is_correct(trained):
    result, obs = trained
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == obs["steps_in_window"] >= 1
    assert result["metrics"] == {}


def test_lower_precision_control_fails_the_training_comparison(trained):
    _, obs = trained
    limits = _limits(obs)
    low = control.control_readings(obs)
    assert any(low[k] > limits[k] for k in limits if k in low), (low, limits)


def test_a_step_that_returns_its_state_unchanged_is_incorrect(monkeypatch):
    import tnn_tpu.train as train

    real = train.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(state, data, labels):
            import jax
            import jax.numpy as jnp

            copy = jax.tree_util.tree_map(jnp.copy, state)
            _, m = step(copy, data, labels)     # the step donates its state
            return state, m

        return broken

    monkeypatch.setattr(train, "make_train_step", make)
    result, obs = _run("gpt2-medium.train", 2 ** 31 + 80, 1)
    assert result["correct"] is False
    assert obs["readings"]["delta_gap"] > _limits(obs)["delta_gap"]
