"""From the JAX profiler's ``.xplane.pb`` to the numbers the per-layer
metrics read, with nothing but JAX (``jax.profiler.ProfileData``).

What a TPU v5e trace holds (seen in PR 23's first chip call): one plane per
chip, ``/device:TPU:<i>``, whose line ``XLA Ops`` has one event per executed
HLO instruction, named by the instruction's whole text
(``%copy.188 = bf16[36,704,20,16,64]{...} copy(...)``); a Pallas kernel is a
``custom-call`` whose instruction is named after the jitted function around
it, because the program gives its kernels no name yet. ``/host:CPU`` has one
line per host thread.

  busy_s    union of the XLA-op intervals, mean over the chips
  window_s  first op's start to last op's end, mean over the chips
  ops       [(instruction text, seconds, count)], summed over chips / chips
  top_ops   the ten largest groups of ops, [(group, seconds)]
  top_gaps  the longest idle gaps between ops, grouped by the host event that
            overlaps each most, [(host event, seconds)]
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
GAPS_ATTRIBUTED = 200       # only the longest gaps are given a host event
_INSTR = re.compile(r"^%?(?P<name>[^ ]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])?"
                    r".*? (?P<op>[a-z][a-z0-9-]*)\(")


def group_of(text: str) -> str:
    """A short, stable label for an instruction: fusions and kernels by
    their name without its number, other ops by opcode and result type."""
    m = _INSTR.match(text)
    if not m:
        return text[:60]
    stem = re.sub(r"[.\d]+$", "", m["name"])
    if m["op"] in ("fusion", "custom-call"):
        return f"{m['op']} {stem}"
    return f"{m['op']} {m['type'] or ''}".strip()


def union(intervals):
    """Merged, sorted (start, end) list of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(pd) -> dict:
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    devices = [d for d in devices if d]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": [], "top_ops": [],
                "top_gaps": [], "chips": 0}
    n = len(devices)
    busy = window = 0.0
    by_text, by_group, gaps = {}, {}, []
    for evs in devices:
        merged = union((s, s + d) for _, s, d in evs)
        busy += sum(e - s for s, e in merged) / 1e9
        window += (merged[-1][1] - merged[0][0]) / 1e9
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for name, _, d in evs:
            t = by_text.setdefault(name, [0.0, 0])
            t[0] += d / 1e9 / n
            t[1] += 1
            g = group_of(name)
            by_group[g] = by_group.get(g, 0.0) + d / 1e9 / n
    by_host = {}
    for length, g0, g1 in sorted(gaps, reverse=True)[:GAPS_ATTRIBUTED]:
        best, best_ov = "(no host event)", 0.0
        for name, h0, h1 in host:
            ov = min(g1, h1) - max(g0, h0)
            if ov > best_ov:
                best, best_ov = name, ov
        by_host[best[:80]] = by_host.get(best[:80], 0.0) + length / 1e9 / n

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:10]]

    return {"busy_s": busy / n, "window_s": window / n, "chips": n,
            "ops": [(k, v[0], v[1]) for k, v in by_text.items()],
            "top_ops": top(by_group), "top_gaps": top(by_host)}


def reduce_file(path) -> dict:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def reduce_dir(trace_dir) -> dict:
    """The newest ``.xplane.pb`` under a directory ``start_trace`` wrote."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(max(files, key=os.path.getmtime))
