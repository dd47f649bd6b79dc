"""Reader ``span_p50``: the median, in ms, of one of the benchmark's own host
spans (``obs["spans"][span]``, seconds), taken around a call into a layer."""
from chipbench import stats


def read(obs, span):
    xs = obs.get("spans", {}).get(span)
    return None if not xs else 1e3 * stats.percentile(xs, 50)
