"""Benchmark utilities: device timing, verification, MFU.

Pattern parity: the reference's benchmark harness verifies numerics against a
reference implementation before timing (benchmarks/gemm_benchmark.cpp:20-33 checks
custom AVX2 GEMM vs MKL) — every benchmark here does the same against numpy/XLA.

Timing syncs on a value fetch and uses difference-of-two-runs (``time_loop``):
time N1 iterations + one fetch, then N2 > N1 iterations + one fetch,
dt = (t2 - t1)/(N2 - N1) — the cost of the sync itself cancels instead of being
subtracted as a separately-sampled constant. On a directly attached chip
``jax.block_until_ready`` does block (chip_smoke.py's device phase checks it),
so a plain host clock around a blocked loop would serve as well; the scheme was
built for a remote backend where it did not.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# bf16 peak of one TPU v5e chip (the hardware this repo benches on)
V5E_BF16_PEAK_FLOPS = 197e12


def sync(x) -> float:
    """True device sync via scalar fetch (first leaf of any pytree)."""
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.ravel(leaf)[0].astype(jnp.float32))


def time_loop(run: Callable[[int], float], iters: int, *, min_delta: float = 0.35,
              pairs: int = 3, cap: int = 4000) -> float:
    """Difference-of-two-runs timing. ``run(n)`` executes n iterations, blocks
    on the last result, and returns elapsed seconds.

    Times N1 iterations + one fetch, then N2 > N1 iterations + one fetch;
    dt = (t2 - t1) / (N2 - N1). The device executes dispatches FIFO
    back-to-back and a fetch of the LAST output waits for all previous
    executions, so the fetch round trip cancels exactly. N2 auto-escalates
    until the delta is well clear of the fetch jitter; the median over
    ``pairs`` fresh pairs rejects stragglers.
    (Single-compiled-scan timing was tried and rejected: chaining iterations
    through the scan carry needs optimization barriers to stop XLA hoisting
    loop-invariant work, and those barriers pin layouts, which distorted conv
    timings 4x.)
    """
    n1 = max(1, iters // 4)
    n2 = max(iters, n1 + 1)
    t1 = run(n1)
    attempts = 0
    while True:
        t2 = run(n2)
        delta = t2 - t1
        attempts += 1
        if delta >= min_delta or n2 >= cap or attempts >= 8:
            break
        n2 = min(cap, int(n2 * min(max(2.0, 0.45 / max(delta, 1e-4)), 8.0)) + 1)
    # ``delta`` was measured at the final n2 (growth only happens on continue)
    dts = [delta / (n2 - n1)] if delta > 0 else []
    for _ in range(pairs - 1):
        ta, tb = run(n1), run(n2)
        if tb - ta > 0:
            dts.append((tb - ta) / (n2 - n1))
    if not dts:
        # fail loudly: a clamped near-zero dt would report trillion-scale
        # throughput into regression.csv instead of an error
        raise RuntimeError(
            f"time_loop: no positive run-pair delta at n1={n1}, n2={n2} "
            f"(last delta {delta * 1e3:.1f} ms) — device stall or the workload "
            f"is too fast for cap={cap}; raise cap or fix the backend")
    dts.sort()
    return dts[len(dts) // 2]


def time_fn(fn: Callable, *args, iters: int = 50, warmup: int = 5) -> float:
    """Mean seconds per call of a jitted fn (device time, via ``time_loop``)."""
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    sync(out)

    def run(n: int) -> float:
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = fn(*args)
        sync(o)
        return time.perf_counter() - t0

    return time_loop(run, iters)


def timing_selfcheck(max_mfu: float = 1.05, min_mfu: float = 1e-4) -> float:
    """Guard the difference-of-two-runs timing scheme with a known-FLOP matmul.

    The scheme assumes the device executes N dispatched steps back-to-back and
    that one scalar fetch waits for all of them. If a backend ever pipelines
    differently (e.g. dropping work), the implied MFU of a plain matmul goes
    impossible (>105% peak) or absurd (<0.01%) — fail loudly instead of
    reporting fiction. Returns implied MFU.

    Off-TPU the check is skipped (no trustworthy peak to compare against, and
    the emulated-bf16 matmuls would just burn CPU time for no signal).
    """
    if jax.devices()[0].platform != "tpu":
        return 0.0
    n = 4096
    x = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    secs = time_fn(f, x, iters=20, warmup=3)
    mfu = (2 * n**3 / secs) / V5E_BF16_PEAK_FLOPS
    if not (min_mfu <= mfu <= max_mfu):
        raise AssertionError(
            f"timing self-check FAILED: {n}x{n} bf16 matmul implies "
            f"{mfu * 100:.1f}% MFU — the dispatch/fetch timing assumption is "
            f"broken on this backend; do not trust these numbers")
    print(f"  timing self-check: {n}x{n} matmul at {mfu * 100:.1f}% MFU (sane)")
    return mfu


def verify(name: str, got, want, rtol: float = 2e-2, atol: float = 2e-2) -> None:
    """Correctness gate before timing (reference: check_match, gemm_benchmark.cpp:20).
    Tolerances default to bf16-friendly bounds."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.max(np.abs(got - want) / (np.abs(want) + 1.0))
    if not np.allclose(got, want, rtol=rtol, atol=atol):
        raise AssertionError(f"{name}: verification FAILED (max rel err {err:.2e})")
    print(f"  {name}: verified (max rel err {err:.2e})")


def report(name: str, seconds: float, flops: Optional[float] = None,
           items: Optional[float] = None, item_name: str = "items",
           extra: Optional[Dict] = None) -> Dict:
    """One result line: ms, GFLOP/s + MFU when flops given, items/s when given."""
    out: Dict = {"bench": name, "ms": seconds * 1e3}
    if flops:
        out["tflops"] = flops / seconds / 1e12
        out["mfu"] = flops / seconds / V5E_BF16_PEAK_FLOPS
    if items:
        out[f"{item_name}_per_s"] = items / seconds
    if extra:
        out.update(extra)
    bits = [f"{name}: {out['ms']:.3f} ms"]
    if flops:
        bits.append(f"{out['tflops']:.1f} TFLOP/s ({out['mfu'] * 100:.1f}% MFU)")
    if items:
        bits.append(f"{out[f'{item_name}_per_s']:.0f} {item_name}/s")
    print("  " + ", ".join(bits))
    return out


# -- persisted A/B artifacts -------------------------------------------------
#
# Artifacts under benchmarks/results/ serve two audiences: tests gate on the
# STRUCTURAL outcome of a run (bench names, exactness flags, config echoes,
# capacity arithmetic, gate_* booleans) while humans read the timing columns.
# Persisting both in one flat dict meant every re-run rewrote the file even
# when nothing a test asserts had moved — pure diff churn from wall-clock
# noise. write_artifact splits each row into a "gated" part (asserted) and an
# "info" part (informational), and skips the rewrite entirely when the gated
# section is unchanged.

#: substring markers for row fields that are measurements (rates, latency
#: quantiles, wall-clock) or scheduling-dependent counters — they land in
#: the artifact's "info" section and are never asserted by tests
INFO_FIELD_MARKERS = (
    "_per_s", "goodput", "_at_slo", "timeline", "duration", "stall",
    "hedge", "migrat", "eject", "retries", "restart", "rejected",
    "accepted", "finished", "terminal", "shed", "tier_hits",
    "tier_demotions", "scale_", "join_failures", "replicas_max",
    "fallback", "pull", "exported", "adopted",
)


def is_info_field(key: str) -> bool:
    """True when an artifact row field is timing/scheduling noise rather than
    a structural outcome tests may gate on. ``gate_*`` fields are always
    structural — they exist precisely to be asserted."""
    if key.startswith("gate_"):
        return False
    if key == "ms" or "_ms" in key:
        return True
    return any(m in key for m in INFO_FIELD_MARKERS)


def write_artifact(path: str, rows, meta: Optional[Dict] = None,
                   label: str = "A/B") -> str:
    """Persist benchmark rows as ``{"gated": {...}, "info": {...}}``.

    ``gated`` carries ``meta`` (structural run config: devices, budgets) plus
    the structural fields of every row; ``info`` carries the generation
    timestamp, platform, and each row's timing fields. When the file already
    exists with an identical gated section the rewrite is SKIPPED — the old
    info (and its timestamp) stays put, so re-running a bench only touches
    the artifact when something a test could assert on actually changed."""
    import json
    import os

    gated_rows, info_rows = [], []
    for r in rows:
        g = {k: v for k, v in r.items() if not is_info_field(k)}
        g.pop("artifact_path", None)   # self-reference, not an outcome
        gated_rows.append(g)
        info_rows.append({k: v for k, v in r.items() if is_info_field(k)})
    gated = dict(meta or {})
    gated["rows"] = gated_rows
    try:
        with open(path) as f:
            if json.load(f).get("gated") == gated:
                print(f"  {label} artifact unchanged (gated fields) "
                      f"-> {path}")
                return path
    except (OSError, ValueError):
        pass
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"gated": gated,
                   "info": {"generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
                            "platform": jax.devices()[0].platform,
                            "rows": info_rows}}, f, indent=2)
    print(f"  {label} artifact -> {path}")
    return path


ROW_FAILED = "row_failed"  # label prefix shared with run_all's rc scan


class RowRunner:
    """Per-row failure isolation for benchmark suites: one broken kernel or
    model must not cost an (often unattended) evidence pass its other rows.
    Failures become labeled ``row_failed:<fn>`` result entries AND count in
    ``.failed`` so __main__ blocks can exit nonzero — scripts that gate on the
    exit code still see the failure."""

    def __init__(self):
        self.results = []
        self.failed = 0

    def add(self, thunk, many: bool = False, label: str = ""):
        # default label = the bench function the thunk calls (first global it
        # names); pass label= when the thunk is not a direct bench_* call
        label = label or next(iter(getattr(thunk, "__code__", None) and
                                   thunk.__code__.co_names or ()), "?")
        try:
            r = thunk()
            if many:
                self.results.extend(r or [])
            elif r:
                self.results.append(r)
        except Exception as e:  # noqa: BLE001 — report and continue
            import traceback

            traceback.print_exc()
            self.failed += 1
            self.results.append({"bench": f"{ROW_FAILED}:{label}",
                                 "error": f"{type(e).__name__}: "
                                          f"{str(e)[:300]}"})
