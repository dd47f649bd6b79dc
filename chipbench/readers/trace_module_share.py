"""Reader ``trace_module_share``: the device time, in %, of the executed
programs (events of the trace's ``XLA Modules`` line) whose name matches
``pattern`` over the device time of all of them: how much of the chip's
working time one kind of step takes (``trace_module_p50`` says how long one
takes). No program in the slice, or no device plane: ``None``; none that
matches among those that ran: 0."""
import re

from chipbench.reduce import xplane_meta


def read(obs, pattern):
    meta = xplane_meta.of(obs)
    if not meta or not meta["modules"]:
        return None
    rx = re.compile(pattern)
    total = sum(m["dur"] for m in meta["modules"])
    mine = sum(m["dur"] for m in meta["modules"] if rx.search(m["name"]))
    return 100.0 * mine / total if total else None
