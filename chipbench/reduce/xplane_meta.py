"""What ``reduce/xplane.py`` cannot reach through ``jax.profiler.ProfileData``:
the scope path of every device op, the programs, and the program's own host
spans, read from the ``.xplane.pb`` a traced run wrote, by its wire format.

``ProfileData`` gives an op event its name (the instruction text) and its
per-event stats. The scope path (``jit(tnn_serve_decode)/h3/kv_write/...``,
what ``jax.named_scope`` and a jitted function's name leave in the program)
is in the file as the ``tf_op`` stat of the op's XEventMetadata, beside
``hlo_category``. The fields read here (tensorflow/tsl ``xplane.proto``):

  XSpace          planes=1
  XPlane          name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
  XLine           id=1 name=2 timestamp_ns=3 events=4 display_name=11
  XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
  XEventMetadata  id=1 name=2 stats=5 display_name=4
  XStatMetadata   id=1 name=2
  XStat           metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7

``read_file(path)`` gives, with seconds on the trace's own clock:

  ops      [{"name", "start", "dur", "tf_op", "category", "chip"}] one per
           executed HLO instruction (line ``XLA Ops`` of each device plane)
  modules  [{"name", "start", "dur", "chip"}] one per executed program (line
           ``XLA Modules``); the name is ``jit_<function>(<fingerprint>)``
  spans    [{"name", "start", "dur", "thread", "stats"}] host events whose
           name starts ``serve.``, ``front.`` or ``train.`` (the program's
           spans: docs/observability.md); ``thread`` is the line's name
           and id (unnamed threads all carry the process's name)
  chips    how many device planes had ops
"""
from __future__ import annotations

import glob
import os
import re
import struct

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("serve.", "front.", "train.")


def _fields(buf, start=0, end=None):
    """(field number, wire type, value) of one message; a length-delimited
    value is a (start, end) pair into ``buf``."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wt = key & 7
        if wt == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wt == 2:
            n = shift = 0
            while True:
                b = buf[i]
                i += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = (i, i + n)
            i += n
        elif wt == 1:
            val = (i, i + 8)
            i += 8
        elif wt == 5:
            val = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield key >> 3, wt, val


def _first_varint(buf, i):
    """The value of a message's first field when that is field 1, a varint
    (an XEvent's ``metadata_id``); else ``None``."""
    if buf[i] != 0x08:
        return None
    val = shift = 0
    while True:
        i += 1
        b = buf[i]
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val
        shift += 7


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names):
    """(name, value) of one XStat; a ``ref`` value names another stat
    metadata entry whose name is the string meant."""
    name = value = None
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(buf[v[0]:v[1]]))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf, span):
    key = val = None
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span):
    """One XPlane: (name, [line spans], {id: event metadata}, {id: stat
    name}); event metadata is (name, {stat: value}) with stats decoded."""
    name, lines, ev_meta_spans, stat_names = "", [], [], {}
    for f, wt, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta_spans.append(v)
        elif f == 5:
            _, val = _map_entry(buf, v)
            sid = sname = None
            for g, _, w in _fields(buf, *val):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = _text(buf, w)
            stat_names[sid] = sname
    ev_meta = {}
    for span_ in ev_meta_spans:
        _, val = _map_entry(buf, span_)
        mid, mname, stats = None, "", {}
        for g, _, w in _fields(buf, *val):
            if g == 1:
                mid = w
            elif g == 2:
                mname = _text(buf, w)
            elif g == 5:
                k, x = _stat(buf, w, stat_names)
                stats[k] = x
        ev_meta[mid] = (mname, stats)
    return name, lines, ev_meta, stat_names


def _line(buf, span):
    """(name, id, timestamp_ns, [event spans]) of one XLine; a host line's
    id is its thread's."""
    name = display = ""
    lid = t0 = 0
    events = []
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            lid = v
        elif f == 2:
            name = _text(buf, v)
        elif f == 11:
            display = _text(buf, v)
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            events.append(v)
    return name or display, lid, t0, events


def _event(buf, span):
    """(metadata id, offset_ps, duration_ps, [stat spans]) of one XEvent."""
    mid = off = dur = 0
    stats = []
    for f, wt, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = _signed(v)
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


def read_bytes(data) -> dict:
    buf = memoryview(data)
    ops, modules, spans = [], [], []
    chips = 0
    for f, wt, v in _fields(buf):
        if f != 1 or wt != 2:
            continue
        pname, lines, ev_meta, stat_names = _plane(buf, v)
        if pname.startswith(DEVICE_PLANE):
            chip = pname[len(DEVICE_PLANE):]
            had_ops = False
            for lspan in lines:
                lname, _, t0, events = _line(buf, lspan)
                if lname not in (OPS_LINE, MODULES_LINE):
                    continue
                for espan in events:
                    mid, off, dur, _ = _event(buf, espan)
                    mname, mstats = ev_meta.get(mid, ("", {}))
                    ev = {"name": mname, "start": t0 * 1e-9 + off * 1e-12,
                          "dur": dur * 1e-12, "chip": chip}
                    if lname == OPS_LINE:
                        ev["tf_op"] = (mstats.get("tf_op") or "").rstrip(":")
                        ev["category"] = mstats.get("hlo_category") or ""
                        ops.append(ev)
                        had_ops = True
                    else:
                        modules.append(ev)
            chips += had_ops
        elif pname == HOST_PLANE:
            wanted = {mid for mid, (n, _) in ev_meta.items()
                      if n.startswith(SPAN_PREFIXES)}
            for lspan in lines:
                lname, lid, t0, events = _line(buf, lspan)
                for espan in events:
                    if _first_varint(buf, espan[0]) not in wanted:
                        continue    # the Python tracer's frames: most of it
                    mid, off, dur, stat_spans = _event(buf, espan)
                    stats = dict(_stat(buf, s, stat_names)
                                 for s in stat_spans)
                    spans.append({"name": ev_meta[mid][0],
                                  "start": t0 * 1e-9 + off * 1e-12,
                                  "dur": dur * 1e-12,
                                  "thread": f"{lname}/{lid}",
                                  "stats": stats})
    return {"ops": ops, "modules": modules, "spans": spans, "chips": chips}


def read_file(path) -> dict:
    with open(path, "rb") as f:
        return read_bytes(f.read())


def newest(trace_dir):
    """The newest ``.xplane.pb`` under a directory ``start_trace`` wrote."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def of(obs):
    """The run's trace, read once and kept on ``obs``; ``None`` for a run
    that was not traced or left no file."""
    if "trace_meta" not in obs:
        ctx = obs.get("ctx")
        path = newest(ctx.trace_dir) if ctx is not None and getattr(
            ctx, "trace_dir", None) else None
        obs["trace_meta"] = read_file(path) if path else None
    return obs["trace_meta"]


def busy_seconds(meta):
    """Union of the device-op intervals, mean over the chips: the same
    number as ``reduce/xplane.py``'s ``busy_s``."""
    by_chip = {}
    for op in meta["ops"]:
        by_chip.setdefault(op["chip"], []).append(
            (op["start"], op["start"] + op["dur"]))
    total = 0.0
    for ivs in by_chip.values():
        end = None
        for s, e in sorted(ivs):
            if end is None or s > end:
                total += e - s
                end = e
            elif e > end:
                total += e - end
                end = e
    return total / len(by_chip) if by_chip else 0.0


def leaf_scope(tf_op):
    """The innermost scope of an op's path, without the jitted functions,
    the block (``h3``) and the primitive:
    ``jit(f)/transpose(jvp(h3))/mlp/dot_general`` -> ``mlp``,
    ``jit(f)/jvp(loss)/jit(log_softmax)/log`` -> ``loss``; a kernel shows
    under its own name; a path with no scope -> ``(none)``; producers joined
    by ``;`` (a layout copy) -> the first one's that has a scope."""
    if tf_op and "/" not in tf_op:
        return tf_op            # a program argument's own layout copy
    in_block = False
    for producer in tf_op.split(";"):
        path = re.sub(r"\bjit\([^()]*\)/?", "", producer)
        path = path.rsplit("/", 1)[0] if "/" in path else ""   # primitive
        parts = [p for p in re.split(r"[/()]", path)
                 if p and p not in ("jvp", "transpose")]
        inner = [p for p in parts if not re.fullmatch(r"h\d+", p)]
        if inner:
            return inner[-1]
        in_block = in_block or bool(parts)
    return "h* (a block, no inner scope)" if in_block else "(none)"


def main(argv=None):
    """``python3 -m chipbench.reduce.xplane_meta <file or directory>``: the
    slice's device time by innermost scope, by program, and its host spans."""
    import sys

    path = (argv or sys.argv[1:])[0]
    meta = read_file(newest(path) if os.path.isdir(path) else path)
    busy = busy_seconds(meta)
    print(f"chips {meta['chips']} ops {len(meta['ops'])} busy {busy:.6f} s")
    by = {}
    for op in meta["ops"]:
        key = leaf_scope(op["tf_op"])
        by[key] = by.get(key, 0.0) + op["dur"] / max(meta["chips"], 1)
    for k, v in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"  scope {k:28s} {v:10.6f} s {100 * v / busy:6.2f}%")
    mods = {}
    for m in meta["modules"]:
        mods.setdefault(m["name"].split("(")[0], []).append(m["dur"])
    for k, xs in sorted(mods.items()):
        xs.sort()
        print(f"  program {k:32s} n {len(xs):4d} median "
              f"{1e3 * xs[len(xs) // 2]:9.3f} ms")
    spans = {}
    for s in meta["spans"]:
        spans.setdefault((s["thread"], s["name"]), []).append(s["dur"])
    for (th, k), xs in sorted(spans.items()):
        xs.sort()
        print(f"  span {th[:24]:24s} {k:20s} n {len(xs):5d} median "
              f"{1e3 * xs[len(xs) // 2]:9.3f} ms")


if __name__ == "__main__":
    main()
