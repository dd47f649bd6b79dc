"""One run of one cell of the benchmark:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, traced ``breakdown``, and
last ``checks``: every number that decided ``correct``, by a short name, with
its limit); the same checks are the last lines of standard error. Everything
else worth reading goes on earlier lines. With no TPU, or fewer chips than
the cell asks for, the command exits 2 and prints no result.

``--rehearse`` runs the same control flow on whatever backend JAX has, at the
configuration's tiny ``rehearsal`` sizes, to debug the harness in a sandbox
without a chip. It says so on its lines and its result carries NO metric
value: a number from a CPU run is never a device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up counts from process start

import argparse     # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

from chipbench import spec      # noqa: E402

TRACE_OFFSET_S = 2.0    # a traced run records a slice this far into the window
TRACE_SECONDS = 3.0     # ... and this long: traces are large


class Ctx:
    """What a driver and a generator get: the cell's data, the run's
    arguments, the window's marks (memory peak, the traced slice), and
    where what decides ``correct`` is written down (``hold``). A driver
    leaves the sizes its reference module gave it in ``sizes``."""

    def __init__(self, workload, config, traffic, args):
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.scale = config["rehearsal"]["scale"] if args.rehearse else 1.0
        self.t_start = T_START
        self.t_open = self.t_close = None
        self.memory_peak_bytes = None
        self.trace_dir = None
        self._tracer = None
        self.sizes = None
        self.checks, self.check_lines = {}, []

    def hold(self, name, got, limit, ok, what=""):
        """One number compared: ``name`` is short and plain, ``what`` says
        it in words on the run's own line."""
        if isinstance(got, float) and not math.isfinite(got):
            got = repr(got)             # the result line stays plain JSON
        self.checks[name] = {"value": got, "limit": limit, "ok": bool(ok)}
        self.check_lines.append(
            f"check {name}: {got} (limit {limit}) "
            f"{'ok' if ok else 'FAIL'}{'  -- ' + what if what else ''}")
        self.note(self.check_lines[-1])

    def note(self, msg):
        tag = "[REHEARSAL, not a chip run] " if self.rehearse else ""
        print(f"chipbench: {tag}{msg}", flush=True)

    def window_opened(self):
        self.t_open = time.perf_counter()
        self.note(f"window open {self.t_open - self.t_start:.3f} s after "
                  "process start")
        if self.trace:
            import threading

            self._tracer = threading.Thread(target=self._trace_slice,
                                            daemon=True)
            self._tracer.start()

    def _trace_slice(self):
        import jax

        self.trace_dir = os.path.join(spec.ROOT, "chiprun_out", "trace",
                                      self.workload["name"])
        shutil.rmtree(self.trace_dir, ignore_errors=True)   # only the newest
        time.sleep(min(TRACE_OFFSET_S, self.seconds / 4))
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.trace_dir)
        time.sleep(min(TRACE_SECONDS, self.seconds / 2))
        jax.profiler.stop_trace()
        self.trace_wall = (t0, time.perf_counter())

    def window_closed(self):
        import jax

        self.t_close = time.perf_counter()
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.local_devices() if d.memory_stats()]
        self.memory_peak_bytes = max(peaks, default=0)

    def finish_trace(self):
        if self._tracer is not None:
            self._tracer.join(120.0)
            if self._tracer.is_alive():
                raise RuntimeError("the profiler did not stop")


def device_info(chips, rehearse):
    """What JAX runs on; refuse anything but the chips the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if info["platform"] != "tpu" or len(devs) < chips:
        print(f"chipbench: this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {info['platform']} ({info['kind']}). No "
              "result.", file=sys.stderr)
        raise SystemExit(2)
    spec.peaks(info["kind"])        # an unknown chip is an error, now
    return info


def facts_of(obs):
    """The run's named numbers for the ``arithmetic`` reader."""
    facts = {f"summary.{k}": v for k, v in obs.get("summary", {}).items()
             if isinstance(v, (int, float))}
    if not obs["ctx"].rehearse:
        facts.update({f"peak.{k}": v for k, v in
                      spec.peaks(obs["device"]["kind"]).items()})
    facts["memory_peak_bytes"] = obs["ctx"].memory_peak_bytes
    facts.update(obs.get("facts", {}))
    return facts


def run_cell(args):
    """One run of one cell: (the result line's object, the observations)."""
    bench = spec.benchmark()
    workload, config, traffic = spec.cell(bench, args.workload)
    spec.check_cut(spec.by_name(bench["configs"], workload["config"],
                                "configuration"), config,
                   spec.plugin("reference", config["reference"]))
    traffic.update(getattr(args, "traffic_override", {}))   # control.py's
    config.update(getattr(args, "config_override", {}))
    import tnn_tpu  # noqa: F401  the system under test: absent -> no result

    device = device_info(workload["chips"], args.rehearse)
    ctx = Ctx(workload, config, traffic, args)
    ctx.note(f"cell {workload['name']} seed {args.seed} seconds "
             f"{args.seconds} trace {args.trace} on {device}")
    driver = spec.plugin("drivers", config["driver"])
    obs = driver.run(ctx)
    ctx.finish_trace()
    obs.update(ctx=ctx, device=device, setup_s=ctx.t_open - ctx.t_start,
               window_s=ctx.t_close - ctx.t_open)

    checks = spec.plugin("drivers", config["driver"] + "_check")
    correct, attempted, failed = checks.judge(obs)
    obs["facts"] = facts_of(obs)

    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": device}
    if args.trace:
        from chipbench.reduce import xplane

        obs["trace"] = xplane.reduce_dir(ctx.trace_dir)
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = {"device_ops": obs["trace"]["top_ops"],
                               "idle_gaps": obs["trace"]["top_gaps"]}
        for m in spec.metrics_of(bench, workload["name"], "per_layer"):
            how = spec.load_json("chipbench", "layer_metrics",
                                 m["name"] + ".json")
            value = spec.plugin("readers", how["reader"]).read(
                obs, **how.get("args", {}))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        for m in spec.metrics_of(bench, workload["name"], "end_to_end"):
            value = spec.plugin("end_to_end", m["name"]).value(obs)
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        for line in checks.also_worth_reading(obs):
            ctx.note(line)
    if args.rehearse:
        ctx.note("values (NOT device metrics): " + json.dumps(
            {k: v["value"] for k, v in result["metrics"].items()}))
        result["metrics"] = {}
        result["rehearsal"] = True
    # last: of a line that is not correct the driver's record keeps the END
    result["checks"] = ctx.checks
    return result, obs


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no metric value")
    return ap.parse_args(argv)


def main(argv=None):
    result, obs = run_cell(parse(argv))
    sys.stdout.flush()
    print("\n".join("chipbench: " + line for line in obs["ctx"].check_lines),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
