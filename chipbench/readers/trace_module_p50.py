"""Reader ``trace_module_p50``: the median device time, in ms, of the
executed programs (events of the trace's ``XLA Modules`` line, named
``jit_<function>(<fingerprint>)``) whose name matches ``pattern``: how long
the chip took for one compiled step, with nothing of the host in it. No such
program in the slice, or no device plane: ``None``."""
import re

from chipbench import stats
from chipbench.reduce import xplane_meta


def read(obs, pattern):
    meta = xplane_meta.of(obs)
    if not meta:
        return None
    rx = re.compile(pattern)
    xs = [m["dur"] for m in meta["modules"] if rx.search(m["name"])]
    return 1e3 * stats.percentile(xs, 50) if xs else None
