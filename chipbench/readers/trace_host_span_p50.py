"""Reader ``trace_host_span_p50``: the median, in ms, of one of the PROGRAM's
host spans (``jax.profiler.TraceAnnotation``: ``serve.*``, ``front.*``,
``train.*``, docs/observability.md) among those recorded in the traced slice,
on the profiler's clock. A program without the span, or a run with no trace:
``None``."""
from chipbench import stats
from chipbench.reduce import xplane_meta


def read(obs, span):
    meta = xplane_meta.of(obs)
    if not meta:
        return None
    xs = [s["dur"] for s in meta["spans"] if s["name"] == span]
    return 1e3 * stats.percentile(xs, 50) if xs else None
