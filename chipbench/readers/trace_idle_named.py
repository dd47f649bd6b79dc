"""Reader ``trace_idle_named``: the share, in %, of the traced slice's idle
time (between the device's ops, from the first to the last span recorded on
the thread that drives the device) that lies inside a span of the program on
that thread: how much of the idle time the program can put a name to. Each instant of that thread belongs to its INNERMOST span
(``serve.admit`` inside ``serve.build``: the admission), and a run's earlier
lines get the table an operator wants, idle seconds by span name.

The driving thread is the one that records ``serve.dispatch`` or
``train.dispatch``. No such span (the parent of the PR that added them), no
device plane, or no idle time at all: ``None``."""
from chipbench.reduce import xplane_meta
from chipbench.reduce.xplane import union

DISPATCH = ("serve.dispatch", "train.dispatch")


def innermost(spans):
    """Disjoint (start, end, name) segments of possibly nested spans: every
    instant goes to the deepest span that covers it."""
    out, stack = [], []         # stack of (end, name), outermost first

    def emit(t0, t1):
        if stack and t1 > t0:
            out.append((t0, t1, stack[-1][1]))

    t = None
    for s in sorted(spans, key=lambda s: (s["start"], -s["dur"])):
        start, end = s["start"], s["start"] + s["dur"]
        while stack and stack[-1][0] <= start:      # spans that ended
            emit(t, stack[-1][0])
            t = stack.pop()[0]
        if stack:
            emit(t, start)
        t = start
        stack.append((end, s["name"]))
    while stack:
        emit(t, stack[-1][0])
        t = stack.pop()[0]
    return out


def idle_by_span(meta):
    """({span name: idle seconds inside it}, all idle seconds) on the first
    chip's ops, or ``None`` where the program left no dispatch span."""
    threads = [s["thread"] for s in meta["spans"] if s["name"] in DISPATCH]
    if not threads or not meta["ops"]:
        return None
    thread = max(set(threads), key=threads.count)
    chip = meta["ops"][0]["chip"]
    busy = union((o["start"], o["start"] + o["dur"])
                 for o in meta["ops"] if o["chip"] == chip)
    segs = innermost([s for s in meta["spans"] if s["thread"] == thread])
    # a span that was open when the recording began is not in it: only the
    # idle time between the thread's first and last recorded span counts
    t0, t1 = segs[0][0], segs[-1][1]
    gaps = [(max(a[1], t0), min(b[0], t1)) for a, b in zip(busy, busy[1:])]
    gaps = [(g0, g1) for g0, g1 in gaps if g1 > g0]
    named, i = {}, 0
    for g0, g1 in gaps:                     # both lists are sorted
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            ov = min(g1, segs[j][1]) - max(g0, segs[j][0])
            if ov > 0:
                named[segs[j][2]] = named.get(segs[j][2], 0.0) + ov
            j += 1
    return named, sum(g1 - g0 for g0, g1 in gaps)


def read(obs):
    meta = xplane_meta.of(obs)
    got = idle_by_span(meta) if meta else None
    if not got or not got[1]:
        return None
    named, idle = got
    table = ", ".join(f"{k} {1e3 * v:.3f}" for k, v in
                      sorted(named.items(), key=lambda kv: -kv[1]))
    obs["ctx"].note(f"idle {1e3 * idle:.3f} ms of the slice, by the driving "
                    f"thread's innermost span (ms): {table or 'none'}; "
                    f"unnamed {1e3 * (idle - sum(named.values())):.3f}")
    return 100.0 * sum(named.values()) / idle
