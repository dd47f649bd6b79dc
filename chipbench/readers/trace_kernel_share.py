"""Reader ``trace_kernel_share``: device time of the operations whose name
matches ``pattern`` (a regular expression, searched) over the device's busy
time in the traced slice."""
import re


def kernel_seconds(trace, pattern):
    rx = re.compile(pattern)
    return sum(s for name, s, _ in trace["ops"] if rx.search(name))


def read(obs, pattern):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = kernel_seconds(tr, pattern)
    return 100.0 * secs / tr["busy_s"] if secs else None
