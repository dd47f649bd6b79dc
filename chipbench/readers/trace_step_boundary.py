"""Reader ``trace_step_boundary``: every idle gap in front of a serving
program, split by what the thread that drives the device was doing, on a
clock on which the device's events and the host's agree.

The traced slice holds two records of one step: the program on the device
(an ``XLA Modules`` event named ``jit_tnn_serve_*``) and the
``serve.dispatch`` span that launched it. The runtime's own host events tie
them: the profile's ``DoEnqueueProgram`` and ``CompleteCallbacks`` carry the
``run_id`` of the program's ``XLA Modules`` event (``runs_of``, a pass of
its own over the recording: ``xplane_meta`` keeps neither). A recording
without them (a CPU's) is not read. Three steps:

1. **Join.** A program's ``DoEnqueueProgram`` is on the HOST's clock, as the
   spans are, and follows the jitted call that asked for it (on the chip by
   0.7-1.4 ms, on a thread of the runtime's: the call returns before its
   program is enqueued): a program belongs to the ``serve.launch`` that
   began last before its enqueue did, one program a launch, and its name
   must be the one its ``serve.dispatch`` says (``program``). A program
   enqueued before the driving thread's first recorded launch, or after its
   last recorded span ended (the runtime's events are recorded for some
   30 ms longer than the thread's spans: one run in six holds a program
   whose launch is no longer in it), is cut by the slice's edge and
   dropped; of the others 99% must be joined.
2. **Clock.** With device time = host time + d: no program starts before it
   is enqueued, so d <= U = min (program start - enqueue start); none ends
   after its completion is called back, so d >= L = max (program end -
   callback start); over every program of the chip, the tiny ones of a key
   split too. Host times are shifted by the value of [L, U] nearest 0. On
   the chip the interval is 0.27 ms wide over half a second and 0.06-0.26
   over a slice of 3 s, because the two clocks are seen to step against
   each other by up to 0.2 ms inside a slice: so L may pass U by
   ``CLOCK_STEP``, the shift is then their midpoint and the note shows
   L > U. Further apart: the clock is broken.
3. **Account.** Every idle gap of the first chip (between the union of its
   ops, from the driving thread's first recorded span to its last, as
   ``trace_idle_named`` takes them) that lies in front of a joined program
   is cut by what the driving thread was in at that instant:

   ``launch_to_start``  after the end of THIS program's ``serve.launch``,
                        whatever span the host is in by then
   ``fetch_return``     before that, inside a ``serve.fetch``: its
                        predecessor's end to the fetch's return
   ``dispatch``         inside ``serve.dispatch`` / ``serve.put`` /
                        ``serve.launch`` (by the innermost span's name: a
                        mixed step's ``serve.put`` inside ``serve.build`` is
                        an upload too)
   ``host``             inside any other span of the thread (``serve.commit``,
                        ``serve.emit``, ``serve.deferred``, ``serve.build``,
                        ``serve.speculate`` outside its dispatch)
   ``other``            no span; a gap between the ops of one program; a gap
                        in front of a program that was not joined

``read(obs, part=...)`` returns that part's share, in %, of the slice's idle
time; ``part="clock"`` the absolute shift applied, in ms; ``before="ahead"``
the share of the idle time that lies in front of programs dispatched behind
an uncommitted step (``ahead`` >= 1 on their ``serve.dispatch``). One note a
run prints L, U, the shift and the whole table in ms, by part and by the
kind of program that follows. A program without ``serve.launch`` spans (the
parent of the PR that added them), a run with no trace, a recording without
the runtime's events, a join or a clock that does not hold: ``None``, and a
note that says which.
"""
import bisect
import os
import re

from chipbench.reduce import xplane_meta
from chipbench.reduce.xplane import union
from chipbench.readers.trace_idle_named import innermost

PROGRAM = re.compile(r"^jit_tnn_serve_")
RUN_ID = "run_id"           # the stat a program shares with the runtime's ...
RUNTIME_EVENTS = ("DoEnqueueProgram", "CompleteCallbacks")  # ... host events
PARTS = ("fetch_return", "host", "dispatch", "launch_to_start", "other")
DISPATCH_SPANS = ("serve.dispatch", "serve.put", "serve.launch")
MIN_JOINED = 0.99
CLOCK_STEP = 0.5e-3         # L may pass U by this: the clocks step, see above
PS = 1e-12                  # the recording's own unit: spans that end together


def follows(stats):
    """The kind of program a gap lies in front of, for the table."""
    kind = str(stats.get("kind", ""))
    if not kind.startswith("decode"):
        return kind
    return "decode ahead" if int(stats.get("ahead", 0)) else "decode built"


def _end(ev):
    return ev["start"] + ev["dur"]


def runs_of(data):
    """The programs of a recording (the bytes of an ``.xplane.pb``) that
    carry a ``run_id``, in order of start: ``[{"name", "start", "dur",
    "chip", "enqueued", "called_back"}]``, the last two the starts, on the
    host's clock, of the ``DoEnqueueProgram`` and the ``CompleteCallbacks``
    with that ``run_id`` (``None`` where the recording has none)."""
    buf = memoryview(data)
    programs, host = [], {name: {} for name in RUNTIME_EVENTS}
    for f, wt, v in xplane_meta._fields(buf):
        if f != 1 or wt != 2:
            continue
        name, lines, ev_meta, stat_names = xplane_meta._plane(buf, v)
        device = name.startswith(xplane_meta.DEVICE_PLANE)
        if not device and name != xplane_meta.HOST_PLANE:
            continue
        wanted = {mid: n for mid, (n, _) in ev_meta.items() if n in host}
        for lspan in lines:
            lname, _, t0, events = xplane_meta._line(buf, lspan)
            if device and lname != xplane_meta.MODULES_LINE:
                continue
            for espan in events:
                if not device and xplane_meta._first_varint(
                        buf, espan[0]) not in wanted:
                    continue
                mid, off, dur, stat_spans = xplane_meta._event(buf, espan)
                run = dict(xplane_meta._stat(buf, sp, stat_names)
                           for sp in stat_spans).get(RUN_ID)
                if run is None:
                    continue
                start = t0 * 1e-9 + off * 1e-12
                if device:
                    programs.append({
                        "name": ev_meta[mid][0], "start": start,
                        "dur": dur * 1e-12, "run": run,
                        "chip": name[len(xplane_meta.DEVICE_PLANE):]})
                else:
                    host[wanted[mid]][run] = start
    enqueued, called_back = (host[name] for name in RUNTIME_EVENTS)
    for p in programs:      # one host: the runs of every chip are its runs
        run = p.pop("run")
        p["enqueued"], p["called_back"] = (enqueued.get(run),
                                           called_back.get(run))
    return sorted(programs, key=lambda p: p["start"])


def _launches(spans):
    """[(dispatch span, its serve.launch)] of one thread in order of start;
    a dispatch whose call raised has no launch that ended inside it."""
    launches = sorted((s for s in spans if s["name"] == "serve.launch"),
                      key=lambda s: s["start"])
    starts = [s["start"] for s in launches]
    out = []
    for d in sorted((s for s in spans if s["name"] == "serve.dispatch"),
                    key=lambda s: s["start"]):
        i = bisect.bisect_left(starts, d["start"])
        if i < len(launches) and _end(launches[i]) <= _end(d) + PS:
            out.append((d, launches[i]))
    return out


def clock(runs):
    """(L, U) of device time - host time over the runs, or ``None``."""
    upper = [r["start"] - r["enqueued"] for r in runs
             if r["enqueued"] is not None]
    lower = [_end(r) - r["called_back"] for r in runs
             if r["called_back"] is not None]
    return (max(lower), min(upper)) if lower and upper else None


def join(programs, launches, recorded_to):
    """``{id(program): (dispatch, launch)}`` of the serving programs that
    were joined, and how many the slice's edges cut: enqueued before the
    first recorded launch began, or after ``recorded_to``, the end of the
    driving thread's last recorded span (or of the host's recording: no
    enqueue at all)."""
    starts = [la["start"] for _, la in launches]
    taken, cut = {}, 0
    for p in programs:
        i = -1 if p["enqueued"] is None or p["enqueued"] > recorded_to \
            else bisect.bisect_right(starts, p["enqueued"]) - 1
        if i < 0:
            cut += 1
        else:
            taken.setdefault(i, []).append(p)
    return {id(ps[0]): launches[i] for i, ps in taken.items()
            if len(ps) == 1 and ps[0]["name"].split("(")[0]
            == f"jit_{launches[i][0]['stats'].get('program')}"}, cut


def account(meta, runs):
    """``{"idle", "parts", "by_next", "ahead", "clock": (L, U, shift),
    "cut", "joined", "programs"}`` in seconds, or (None, why).
    ``runs``: ``runs_of`` the same recording."""
    threads = [s["thread"] for s in meta["spans"]
               if s["name"] == "serve.dispatch"]
    if not threads or not meta["ops"]:
        return None, "no serve.dispatch span or no device op in the slice"
    thread = max(set(threads), key=threads.count)
    spans = [s for s in meta["spans"] if s["thread"] == thread]
    launches = _launches(spans)
    if not launches:
        return None, "the program leaves no serve.launch span"
    chip = meta["ops"][0]["chip"]
    runs = [r for r in runs or () if r["chip"] == chip]
    bounds = clock(runs)
    if bounds is None:
        return None, ("the recording holds no " + " / ".join(RUNTIME_EVENTS)
                      + " that shares a run_id with a program")
    lower, upper = bounds
    if lower > upper + CLOCK_STEP:
        return None, (f"the clock is broken: L {1e3 * lower:.3f} ms > U "
                      f"{1e3 * upper:.3f} ms")
    programs = [r for r in runs if PROGRAM.search(r["name"])]
    joined, cut_by_edges = join(programs, launches,
                                max(_end(s) for s in spans))
    whole = len(programs) - cut_by_edges
    if not joined or len(joined) < MIN_JOINED * whole:
        return None, (f"{len(joined)} of {whole} serving programs joined to "
                      f"a serve.dispatch that names them: under "
                      f"{100 * MIN_JOINED:.0f}%")
    d = min(max(0.0, lower), upper) if lower <= upper \
        else (lower + upper) / 2

    # from here on every host time is on the device's clock
    segs, seen = [], float("-inf")
    for t0, t1, name in innermost(spans):
        t0 = max(t0, seen)      # nested spans that end together, as rounded
        if t1 > t0:
            segs.append((t0 + d, t1 + d, name))
            seen = t1
    busy = union((o["start"], _end(o)) for o in meta["ops"]
                 if o["chip"] == chip)
    t0, t1 = segs[0][0], segs[-1][1]
    gaps = [(max(a[1], t0), min(b[0], t1)) for a, b in zip(busy, busy[1:])]
    gaps = [(g0, g1) for g0, g1 in gaps if g1 > g0]

    parts = dict.fromkeys(PARTS, 0.0)
    by_next, ahead = {}, 0.0
    seg_ends = [s[1] for s in segs]

    def book(part, nxt, seconds):
        if seconds > 0:
            parts[part] += seconds
            row = by_next.setdefault(nxt, dict.fromkeys(PARTS, 0.0))
            row[part] += seconds

    # a program's event opens when the device takes the program up, which
    # can be a millisecond before its first op runs (its inputs are still on
    # their way): a gap lies in front of the program whose FIRST OP ends it
    op_starts = sorted(o["start"] for o in meta["ops"] if o["chip"] == chip)
    firsts = []
    for p in programs:
        i = bisect.bisect_left(op_starts, p["start"])
        firsts.append(op_starts[i] if i < len(op_starts)
                      and op_starts[i] < _end(p) else p["start"])
    for g0, g1 in gaps:
        # programs[j]: the first whose first op starts where the gap ends or
        # later (the tiny programs of a key split in front of it are its
        # dispatch's too). A gap that ends before programs[j - 1] does lies
        # between two ops of that one
        j = bisect.bisect_left(firsts, g1)
        nxt = programs[j] if j < len(programs) else None
        inside = j > 0 and g1 < _end(programs[j - 1])
        if inside or nxt is None or id(nxt) not in joined:
            # past the last program, or in front of one that the slice's
            # edges cut from its launch: nothing to cut it by
            book("other", "inside a program" if inside else "unjoined",
                 g1 - g0)
            continue
        dispatch, launch = joined[id(nxt)]
        kind = follows(dispatch["stats"])
        if int(dispatch["stats"].get("ahead", 0)):
            ahead += g1 - g0
        cut = min(max(_end(launch) + d, g0), g1)
        book("launch_to_start", kind, g1 - cut)
        named = 0.0
        i = bisect.bisect_right(seg_ends, g0)
        while i < len(segs) and segs[i][0] < cut:
            ov = min(cut, segs[i][1]) - max(g0, segs[i][0])
            name = segs[i][2]
            book("fetch_return" if name == "serve.fetch" else
                 "dispatch" if name in DISPATCH_SPANS else "host", kind, ov)
            named += max(ov, 0.0)
            i += 1
        book("other", kind, cut - g0 - named)
    return {"idle": sum(g1 - g0 for g0, g1 in gaps), "parts": parts,
            "by_next": by_next, "ahead": ahead, "clock": (lower, upper, d),
            "cut": cut_by_edges, "joined": len(joined),
            "programs": len(programs)}, ""


def table(got):
    """The account in words: the join, the clock, the idle ms by part, and
    by the kind of program that follows."""
    lower, upper, d = got["clock"]
    rows = "; ".join(
        f"before {nxt}: " + ", ".join(
            f"{p} {1e3 * row[p]:.3f}" for p in PARTS if row[p] >= 5e-7)
        for nxt, row in sorted(got["by_next"].items())
        if max(row.values()) >= 5e-7)       # what would print as 0.000
    return (f"step boundary: {got['joined']} of {got['programs']} serving "
            f"programs joined to their serve.dispatch by run_id "
            f"({got['cut']} cut by the slice's edges); "
            f"clock L {1e3 * lower:.3f} U {1e3 * upper:.3f} shift "
            f"{1e3 * d:.3f} ms; idle {1e3 * got['idle']:.3f} ms: "
            + ", ".join(f"{p} {1e3 * got['parts'][p]:.3f}" for p in PARTS)
            + f"; in front of programs dispatched ahead "
            f"{1e3 * got['ahead']:.3f}; {rows}")


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def _runs(obs):
    """``runs_of`` the run's trace, read once and kept on ``obs``, as
    ``xplane_meta.of`` keeps what it reads of the same file."""
    if "trace_runs" not in obs:
        obs["trace_runs"] = runs_of(_read(xplane_meta.newest(
            obs["ctx"].trace_dir)))
    return obs["trace_runs"]


def of(obs):
    """The run's account, worked out once and kept on ``obs`` with its
    note; ``None`` where there is nothing to read."""
    if "step_boundary" not in obs:
        meta = xplane_meta.of(obs)
        got, why = account(meta, _runs(obs)) if meta else (None, "")
        obs["step_boundary"] = got
        if got is not None:
            obs["ctx"].note(table(got))
        elif why:
            obs["ctx"].note(f"step boundary: not read: {why}")
    return obs["step_boundary"]


def read(obs, part=None, before=None):
    got = of(obs)
    if got is None:
        return None
    if part == "clock":
        return 1e3 * abs(got["clock"][2])
    if not got["idle"]:
        return None
    seconds = got["ahead"] if before == "ahead" else got["parts"][part]
    return 100.0 * seconds / got["idle"]


def main(argv=None):
    """``python3 -m chipbench.readers.trace_step_boundary <file or
    directory>``: the boundary table of a recorded profile, no viewer."""
    import sys

    path = (argv or sys.argv[1:])[0]
    data = _read(xplane_meta.newest(path) if os.path.isdir(path) else path)
    got, why = account(xplane_meta.read_bytes(data), runs_of(data))
    print(table(got) if got else f"step boundary: not read: {why}")


if __name__ == "__main__":
    main()
