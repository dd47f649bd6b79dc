"""The control of ``correct``: the readings that its limits are set from.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 --seconds 20

For each seed, in ONE process (one process holds the chip): a short run of
the cell as ``chipbench.run`` makes it, whose own comparison gives the sound
readings; then the control on the same prompts and tokens (or the same three
batches): the plain reference computed in int8 and in fp8 (e4m3), the
precisions next below the bf16 the configurations state, put in the program's
place. For a served
model the control does not decode: at each position it is the token the
lower-precision reference puts first whose gap is read. The benchmark's own runs never call
this; its table goes into PERF.md beside each limit.

``--program-flags="--kv-dtype int8"`` appends flags to the configuration's (a
flag given twice takes its last value): the program with a lower-precision
path of its own switched on is then the control, and its row's ``sound``
readings are that path's. ``--traffic '{"rate_per_s": 0.3}'`` overrides keys
of the mix. The two together are how PERF.md's sweeps were made (an open
loop's knee; rows and blocks of the decode cell), one process a point because
a process's memory peak never falls. ``--trace 1`` adds the point's per-layer
metrics and, per executed program, how often each large copy ran.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench import run as bench_run
from chipbench import spec


def control_readings(obs, precision=None):
    precision = precision or obs["ctx"].config["control_precision"]
    if obs["kind"] == "serve":
        from chipbench.drivers import serve_stdin_check as chk

        sample = chk.sample_requests(
            obs, obs["ctx"].config["check"]["sample_requests"])
        gmax, gmean, n = chk.gap_readings(obs, sample, control=precision)
        return {"gap_max": gmax, "gap_mean": gmean, "tokens": n}
    from chipbench.drivers import train_lm_check as chk

    return chk.gaps(chk.reference_steps(obs, quant=precision),
                    chk.reference_steps(obs))


SUMMARY_KEYS = ("steps", "decode_tokens", "prefill_tokens", "preemptions",
                "step_latency_ms_p50", "step_latency_ms_p99",
                "batch_fill_mean", "pool_occupancy_max")
LARGE_COPY_S = 1e-3     # a copy of a pool takes milliseconds, others do not


def large_copies(obs):
    """{program: runs} and {copy's result type: runs} in the traced slice:
    how many whole-pool copies a step program really makes on the chip."""
    from chipbench.reduce import xplane, xplane_meta

    programs, copies = {}, {}
    for m in xplane_meta.of(obs)["modules"]:
        name = m["name"].split("(")[0]
        programs[name] = programs.get(name, 0) + 1
    for text, seconds, count in obs["trace"]["ops"]:
        group = xplane.group_of(text)
        if group.startswith("copy ") and seconds / count >= LARGE_COPY_S:
            copies[group] = copies.get(group, 0) + count
    return {"programs": programs, "copies": copies}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=1,
                    help="0: sound readings only")
    ap.add_argument("--traffic", default="{}",
                    help="a JSON object of keys of the mix to override")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--program-flags", default="",
                    help="appended to the configuration's program_flags")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    traffic = json.loads(args.traffic)
    for seed in (int(s) for s in args.seeds.split(",")):
        a = bench_run.parse(["--workload", args.workload, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace",
                             str(args.trace)]
                            + (["--rehearse"] if args.rehearse else []))
        a.traffic_override = traffic
        if args.program_flags:
            config = spec.cell(spec.benchmark(), args.workload)[1]
            part = config["rehearsal"] if args.rehearse else config
            part["program_flags"] = (part["program_flags"]
                                     + args.program_flags.split())
            a.config_override = config
        result, obs = bench_run.run_cell(a)
        row = {"seed": seed, "traffic": traffic,
               "program_flags": args.program_flags,
               "correct": result["correct"],
               "attempted": result["attempted"],
               "failed": result["failed"], "sound": obs["readings"],
               "values": {m["name"]: spec.plugin(
                   "end_to_end", m["name"]).value(obs)
                   for m in spec.metrics_of(spec.benchmark(),
                                            args.workload, "end_to_end")},
               "memory_peak_bytes": obs["ctx"].memory_peak_bytes,
               "summary": {k: v for k, v in obs.get("summary", {}).items()
                           if k in SUMMARY_KEYS}}
        if args.trace and not args.rehearse:
            row["per_layer"] = {k: v["value"]
                                for k, v in result["metrics"].items()}
            row["large_copies"] = large_copies(obs)
        if args.control:
            row["control"] = {p: control_readings(obs, p)
                              for p in ("int8", "fp8")}
        print("CONTROL-ROW " + json.dumps(row), flush=True)
        del obs, result
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
