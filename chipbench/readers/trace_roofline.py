"""Reader ``trace_roofline``: the least time the chip could take for the
work a kernel had to do in the traced slice, over the kernel's device time
there. The kernel is found by its NAME: ``pattern`` is a regular expression
searched in the instruction's name (what a kernel's ``name=`` becomes,
``tnn_paged_attention.3``), as ``trace_scope_share`` does with ``by:
"name"``; "every custom call of the program" would take a second kernel for
the first. The work (operations and bytes the algorithm needs, from shapes)
comes from ``chipbench/opcount/<opcount>.py``; the least time is the larger
of operations over the peak rate and bytes over the peak bandwidth of
``chipbench/peaks.json``. A share over 100% is a fault in the count."""
import re

from chipbench import spec
from chipbench.readers.trace_scope_share import instruction


def kernel_seconds(trace, pattern):
    rx = re.compile(pattern)
    return sum(s for text, s, _ in trace["ops"]
               if rx.search(instruction(text)))


def read(obs, pattern, opcount):
    tr = obs.get("trace")
    if not tr:
        return None
    secs = kernel_seconds(tr, pattern)
    work = spec.plugin("opcount", opcount).work_in_slice(obs, pattern)
    if not secs or work is None:
        return None
    peaks = spec.peaks(obs["device"]["kind"])
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
