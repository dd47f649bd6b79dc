"""Reader ``trace_scope_share``: device time of the operations that a scope
of the program names, over the device's busy time in the traced slice, in %.

``include`` and ``exclude`` are regular expressions, searched in an op's
scope path (its ``tf_op``: ``jit(tnn_serve_decode)/h3/kv_write/scatter``; a
backward op carries ``transpose(jvp(h3))/...``, so one word finds forward and
backward; a layout copy the compiler made carries its producers' paths joined
by ``;``) or, with ``by`` = ``"name"``, in the instruction's name (what a
kernel's ``name=`` becomes). An op counts when ``include`` matches (or is not
given) and ``exclude`` does not. Nothing matched, or no device plane (a
rehearsal, a parent with no such scope): ``None``."""
import re

from chipbench.reduce import xplane_meta


def instruction(text):
    """``%tnn_flash_fwd.3 = bf16[...] custom-call(...)`` -> its name."""
    return text.split(" = ", 1)[0].lstrip("%")


def matched_seconds(meta, include=None, exclude=None, by="tf_op"):
    inc = re.compile(include) if include else None
    exc = re.compile(exclude) if exclude else None
    total = 0.0
    for op in meta["ops"]:
        key = instruction(op["name"]) if by == "name" else op["tf_op"]
        if (inc is None or inc.search(key)) and not (exc and exc.search(key)):
            total += op["dur"]
    return total / max(meta["chips"], 1)


def read(obs, include=None, exclude=None, by="tf_op"):
    meta = xplane_meta.of(obs)
    if not meta or not meta["ops"]:
        return None
    busy = xplane_meta.busy_seconds(meta)
    secs = matched_seconds(meta, include, exclude, by)
    return 100.0 * secs / busy if secs and busy else None
