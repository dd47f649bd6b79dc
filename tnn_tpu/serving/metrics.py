"""Serving metrics: TTFT, per-token latency, queue depth, pool occupancy,
throughput — counters and reservoirs where the work happens, and a
Prometheus exposition. (Spans are ``tracing.Tracer``'s; nothing here touches
a profiler.)

Two exposition surfaces share one observation path:

- ``summary()`` — the flat dict ``engine.stats()`` and ``GET /v1/stats``
  report.
- ``prometheus_series()`` — counter/gauge/histogram families rendered by
  ``render_prometheus`` into text-format 0.0.4 for ``GET /metrics``; the
  Router merges per-replica families under a ``replica`` label.

Every ``_tick`` key MUST be registered in ``EXPOSITION`` (tick key →
(prometheus name, type, help, summary key)); the ``unregistered-metric-key``
lint rule fails the build on silent metric drift.

Latency sample series are capped by a fixed-size deterministic reservoir
(Algorithm R with a per-series seeded RNG) so a days-long serve cannot OOM
the host; percentiles stay stable within sampling tolerance.
"""
from __future__ import annotations

import math
import random
import time
from typing import Dict, Iterator, List, Optional, Tuple

from ..profiling.profiler import Profiler

#: default per-series sample cap (reservoir size). Large enough that the
#: smoke/bench workloads never evict (their aggregates stay exact), small
#: enough that a sustained run holds a few hundred KB of floats total.
RESERVOIR_SIZE = 2048

#: fixed histogram bucket upper bounds (seconds) for the latency families.
#: Fixed — not adaptive — so scrapes from different replicas/restarts are
#: always mergeable and dashboards never see bucket churn.
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

#: The exposition registry: every ``_tick`` key maps to
#: ``(prometheus name, type, help, summary key)`` where ``type`` is
#: "counter" (cumulative sum of ticked values) or "histogram" (the tick's
#: value stream also feeds a fixed-bucket histogram), and ``summary key``
#: names the ``summary()`` entry through which the series is reachable.
#: The ``unregistered-metric-key`` lint rule cross-checks all three:
#: ticked keys must appear here, and the named summary keys must appear
#: as literals in ``summary()``.
EXPOSITION: Dict[str, Tuple[str, str, str, str]] = {
    "serve.ttft_s": (
        "tnn_serve_ttft_seconds", "histogram",
        "Time to first token per request", "ttft_ms_p50"),
    "serve.token_latency_s": (
        "tnn_serve_token_latency_seconds", "histogram",
        "Per-token decode latency (step wall time per emitted token)",
        "token_latency_ms_p50"),
    "serve.step_latency_s": (
        "tnn_serve_step_latency_seconds", "histogram",
        "Engine step wall time", "step_latency_ms_p50"),
    "serve.queue_wait_s": (
        "tnn_serve_queue_wait_seconds", "histogram",
        "Time spent QUEUED before (each) admission", "queue_wait_ms_p50"),
    "serve.prefill_chunks": (
        "tnn_serve_prefill_chunks_total", "counter",
        "Prompt chunks pushed inside mixed steps", "prefill_chunks"),
    "serve.prefix_tokens_saved": (
        "tnn_serve_prefix_tokens_saved_total", "counter",
        "Prompt tokens served from cached KV (prefill skipped)",
        "prefill_tokens_saved"),
    "serve.prefix_cows": (
        "tnn_serve_prefix_cows_total", "counter",
        "Copy-on-write block copies at full-cover prefix hits",
        "prefix_cows"),
    "serve.mixed_step_fill": (
        "tnn_serve_mixed_step_fill_total", "counter",
        "Cumulative mixed-step fill ratio (live tokens / compiled capacity)",
        "mixed_step_fill_mean"),
    "serve.decode_stall_s": (
        "tnn_serve_decode_stall_seconds_total", "counter",
        "Cumulative wall gap between token-emitting steps",
        "decode_stall_ms_p50"),
    "serve.host_gap_s": (
        "tnn_serve_host_gap_seconds_total", "counter",
        "Cumulative wall gap between a step's result fetch and the next "
        "dispatch (device idle on host bookkeeping)", "host_gap_ms_p50"),
    "serve.build_s": (
        "tnn_serve_build_seconds_total", "counter",
        "Cumulative host seconds building a step (serve.build: deadline "
        "expiry, scheduling, admissions, input staging up to the first "
        "launch)", "build_ms_p50"),
    "serve.commit_s": (
        "tnn_serve_commit_seconds_total", "counter",
        "Cumulative host seconds committing a step after its fetch "
        "(serve.commit: pool and scheduler state, stop checks, events)",
        "commit_ms_p50"),
    "serve.put_s": (
        "tnn_serve_put_seconds_total", "counter",
        "Cumulative host seconds staging step inputs on the device "
        "(serve.put: every device_put of one launch)", "put_ms_p50"),
    "serve.launch_s": (
        "tnn_serve_launch_seconds_total", "counter",
        "Cumulative host seconds inside the jitted call of a step program "
        "(serve.launch: argument flattening and the asynchronous enqueue)",
        "launch_ms_p50"),
    "serve.emit_delay_s": (
        "tnn_serve_emit_delay_seconds_total", "counter",
        "Cumulative seconds token events waited between their step's "
        "commit and the front end's flush that wrote them",
        "emit_delay_ms_p50"),
    "serve.front_late_s": (
        "tnn_serve_front_late_seconds_total", "counter",
        "Cumulative seconds by which the front end's empty stdin polls "
        "overran the time they asked for (a thread that touches no device: "
        "the machine's stalls seen from inside)", "front_late_ms_total"),
    "serve.overlap_rebuild": (
        "tnn_serve_overlap_rebuilds_total", "counter",
        "Speculatively dispatched steps rolled back on misprediction",
        "overlap_rebuilds"),
    "serve.adopted_step": (
        "tnn_serve_adopted_steps_total", "counter",
        "Steps dispatched ahead of their predecessor's commit and adopted "
        "as dispatched (no build, no host gap)", "adopted_step_share"),
    "serve.adopted_mixed_step": (
        "tnn_serve_adopted_mixed_steps_total", "counter",
        "Adopted steps that push a prompt chunk (a mixed step dispatched "
        "behind its predecessor's commit)", "adopted_mixed_steps"),
    "serve.adopted_decode_step": (
        "tnn_serve_adopted_decode_steps_total", "counter",
        "Adopted steps of decode rows alone", "adopted_decode_steps"),
    "serve.decode_s": (
        "tnn_serve_decode_seconds_total", "counter",
        "Cumulative decode-step wall seconds", "tok_per_s"),
    "serve.spec_accepted": (
        "tnn_serve_spec_accepted_total", "counter",
        "Drafted tokens accepted by the speculative verifier",
        "spec_accepted_tokens"),
    "serve.preemptions": (
        "tnn_serve_preemptions_total", "counter",
        "Recompute preemptions (pool pressure victims)", "preemptions"),
    "serve.shed": (
        "tnn_serve_shed_total", "counter",
        "Queued requests displaced by higher-priority arrivals",
        "shed_requests"),
    "serve.engine_restarts": (
        "tnn_serve_engine_restarts_total", "counter",
        "Supervisor-driven engine recoveries", "engine_restarts"),
    "serve.migrated_requests": (
        "tnn_serve_migrated_requests_total", "counter",
        "Requests re-admitted after an engine restart or replica failover",
        "migrated_requests"),
    "serve.router_retries": (
        "tnn_serve_router_retries_total", "counter",
        "Router-level dispatch retries", "router_retries"),
    "serve.hedges_fired": (
        "tnn_serve_hedges_fired_total", "counter",
        "Requests duplicated onto a second replica past the TTFT hedge "
        "threshold", "hedges_fired"),
    "serve.hedges_won": (
        "tnn_serve_hedges_won_total", "counter",
        "Hedge races the duplicate stream won (first token or promotion "
        "after primary death)", "hedges_won"),
    "serve.hedges_cancelled": (
        "tnn_serve_hedges_cancelled_total", "counter",
        "Hedge losers cancelled/discarded once the race resolved",
        "hedges_cancelled"),
    "serve.degraded_ejections": (
        "tnn_serve_degraded_ejections_total", "counter",
        "Replicas ejected from placement as DEGRADED (gray failure)",
        "degraded_ejections"),
    "serve.proactive_migrations": (
        "tnn_serve_proactive_migrations_total", "counter",
        "Live streams proactively migrated off degraded replicas",
        "proactive_migrations"),
    "serve.drain_duration_s": (
        "tnn_serve_drain_seconds_total", "counter",
        "Wall seconds spent in graceful drains", "drain_duration_s"),
    "serve.publish_suspended": (
        "tnn_serve_publish_suspended_total", "counter",
        "Prefix publishes skipped under pool pressure", "publish_suspended"),
    "serve.rejected": (
        "tnn_serve_rejected_total", "counter",
        "Submits rejected by bounded admission", "rejected"),
    "serve.cancelled": (
        "tnn_serve_cancelled_total", "counter",
        "Requests cancelled by clients", "cancelled"),
    "serve.timed_out": (
        "tnn_serve_timed_out_total", "counter",
        "Requests that hit deadline_s / max_queue_s", "timed_out"),
    "serve.failed": (
        "tnn_serve_failed_total", "counter",
        "Requests failed by isolated faults", "failed"),
    "serve.step_retries": (
        "tnn_serve_step_retries_total", "counter",
        "Transient decode faults retried in place", "step_retries"),
    "serve.kv_bytes_per_token": (
        "tnn_serve_kv_bytes_per_token", "gauge",
        "Page-array bytes one resident KV token costs (K+V, all layers; "
        "int8 scale sidecars excluded)", "kv_bytes_per_token"),
    "serve.tp_degree": (
        "tnn_serve_tp_degree", "gauge",
        "Tensor-parallel degree of this engine (attention heads and KV "
        "pool head-sharded over tp chips; 1 = single-chip)", "tp_degree"),
    "serve.sp_degree": (
        "tnn_serve_sp_degree", "gauge",
        "Sequence-parallel degree of this engine (KV blocks sharded "
        "position-wise over a context mesh of sp chips; 1 = single-chip)",
        "sp_degree"),
    "serve.tier_hits": (
        "tnn_serve_tier_hits_total", "counter",
        "KV blocks re-admitted from the host-RAM tier (digest-verified "
        "device_put instead of recomputed prefill)", "tier_hits"),
    "serve.tier_corrupt": (
        "tnn_serve_tier_corrupt_total", "counter",
        "Host-tier entries dropped at readmit because their integrity "
        "digest failed (degraded to an uncached miss)", "tier_corrupt"),
    "serve.tier_blocks": (
        "tnn_serve_tier_blocks", "gauge",
        "KV blocks currently resident in the host-RAM tier", "tier_blocks"),
    "serve.tier_bytes": (
        "tnn_serve_tier_bytes", "gauge",
        "Host-RAM bytes held by demoted KV blocks (int8 blocks cost about "
        "half their f32 footprint)", "tier_bytes"),
    "serve.replicas": (
        "tnn_serve_replicas", "gauge",
        "Active (non-retired, non-dead) replicas in the fleet — the "
        "autoscaler's actuated value", "replicas"),
    "serve.handoff_exported": (
        "tnn_serve_handoff_exported_blocks_total", "counter",
        "KV blocks serialized for cross-replica handoff (device or host-"
        "tier staged, digest attached)", "handoff_exported_blocks"),
    "serve.handoff_adopted": (
        "tnn_serve_handoff_adopted_blocks_total", "counter",
        "Wire KV blocks adopted after digest verification (prefill those "
        "positions never recompute)", "handoff_adopted_blocks"),
    "serve.handoff_corrupt": (
        "tnn_serve_handoff_corrupt_total", "counter",
        "Wire KV blocks dropped at adopt because their integrity digest "
        "failed (handoff degraded to recompute-resume)", "handoff_corrupt"),
    "serve.boundary_handoffs": (
        "tnn_serve_boundary_handoffs_total", "counter",
        "Requests handed prefill->decode across replicas at the first-"
        "token boundary", "boundary_handoffs"),
    "serve.handoff_fallbacks": (
        "tnn_serve_handoff_fallbacks_total", "counter",
        "Boundary handoffs whose KV shipment failed or fell short — the "
        "stream continued via token-exact recompute-resume",
        "handoff_fallbacks"),
    "serve.fleet_prefix_pulls": (
        "tnn_serve_fleet_prefix_pulls_total", "counter",
        "Admissions whose prefix KV was pulled from a peer replica via "
        "the fleet chain-key directory instead of recomputed",
        "fleet_prefix_pulls"),
}

#: direct (non-``_tick``) families: attribute/gauge name → (prometheus
#: name, type, help). Rendered alongside the EXPOSITION families.
_DIRECT_FAMILIES: Tuple[Tuple[str, str, str, str], ...] = (
    ("finished", "tnn_serve_requests_finished_total", "counter",
     "Requests finished normally"),
    ("decode_tokens", "tnn_serve_decode_tokens_total", "counter",
     "Tokens emitted by decode steps"),
    ("prefill_tokens", "tnn_serve_prefill_tokens_total", "counter",
     "Prompt tokens pushed through prefill"),
    ("steps", "tnn_serve_steps_total", "counter",
     "Engine steps executed"),
    ("step_latency_max_s", "tnn_serve_step_latency_max_seconds", "gauge",
     "Longest engine step wall time since this registry was created"),
    ("front_late_max_s", "tnn_serve_front_late_max_seconds", "gauge",
     "Longest overrun of one empty stdin poll of the front end"),
    ("attn_query_tiles_computed", "tnn_serve_attn_query_tiles_computed_total",
     "counter", "Query tiles the paged kernel computed (the first alone of "
     "a row that fits it, else all) in launches wider than one tile"),
    ("attn_query_tiles_held", "tnn_serve_attn_query_tiles_total", "counter",
     "Query tiles held by paged launches wider than one tile"),
    ("state_slots_occupancy_max", "tnn_serve_state_slots_occupancy_max",
     "gauge", "Largest share of the pool's state slots held by running "
     "requests (a model with layers that keep a state)"),
    ("state_bytes", "tnn_serve_state_bytes", "gauge",
     "Bytes of the pool's state arrays, live states and snapshots (a model "
     "with layers that keep a state)"),
    ("state_snapshots", "tnn_serve_state_snapshots_total", "counter",
     "Row states kept in a snapshot slot by a dispatched step"),
    ("state_restores", "tnn_serve_state_restores_total", "counter",
     "Rows whose live state a rolled-back chain put back from a snapshot "
     "(or from its first token)"),
    ("state_replayed_tokens", "tnn_serve_state_replayed_tokens_total",
     "counter", "Committed tokens pushed again behind a restored state"),
)


def _finite(xs) -> List[float]:
    """Drop NaN/inf samples — a poisoned or clock-skewed observation must
    degrade one sample, not the whole aggregate."""
    return [x for x in xs if math.isfinite(x)]


def _percentile(xs, q: float) -> float:
    """Nearest-rank percentile without a numpy dependency on the hot path.
    NaN-safe: non-finite samples are ignored and an empty (or all-NaN)
    series reports 0.0 instead of raising/propagating NaN — a cache-only
    run with zero decode steps must not crash ``engine.stats()``."""
    ys = sorted(_finite(xs))
    if not ys:
        return 0.0
    idx = min(len(ys) - 1, max(0, int(round(q / 100.0 * (len(ys) - 1)))))
    return ys[idx]


def _mean(xs) -> float:
    """NaN-safe mean over the finite samples; 0.0 when none exist."""
    ys = _finite(xs)
    return sum(ys) / len(ys) if ys else 0.0


def _max(xs) -> float:
    """NaN-safe max over the finite samples; 0.0 when none exist."""
    return max(_finite(xs), default=0.0)


class Reservoir:
    """Fixed-size uniform sample of an unbounded stream (Algorithm R).

    Drop-in for the previous unbounded lists: supports ``append``, ``len``,
    iteration, and ``max(..., default=)``. The RNG is seeded from the
    series name, so a given observation sequence always retains the same
    samples — metric aggregates stay run-to-run deterministic. Below the
    cap the reservoir IS the full series (aggregates exact); above it,
    percentiles hold within sampling tolerance while memory stays flat.
    """

    __slots__ = ("cap", "_items", "_seen", "_rng")

    def __init__(self, name: str = "", cap: int = RESERVOIR_SIZE):
        if cap < 1:
            raise ValueError("reservoir cap must be >= 1")
        self.cap = int(cap)
        self._items: List[float] = []
        self._seen = 0
        self._rng = random.Random(name)

    def append(self, x: float) -> None:
        self._seen += 1
        if len(self._items) < self.cap:
            self._items.append(x)
            return
        j = self._rng.randrange(self._seen)
        if j < self.cap:
            self._items[j] = x

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self) -> Iterator[float]:
        return iter(self._items)

    @property
    def seen(self) -> int:
        """Observations ever appended (>= len once the cap is hit)."""
        return self._seen


class Histogram:
    """Fixed-bucket cumulative histogram (Prometheus classic shape)."""

    __slots__ = ("buckets", "counts", "total", "count")

    def __init__(self, buckets: Tuple[float, ...] = LATENCY_BUCKETS_S):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)   # +1 for +Inf
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        if not math.isfinite(value):
            return
        self.count += 1
        self.total += value
        for i, ub in enumerate(self.buckets):
            if value <= ub:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def samples(self) -> List[Tuple[str, Dict[str, str], float]]:
        """Prometheus sample tuples: cumulative ``_bucket`` series plus
        ``_sum`` and ``_count``."""
        out: List[Tuple[str, Dict[str, str], float]] = []
        cum = 0
        for ub, n in zip(self.buckets, self.counts):
            cum += n
            out.append(("_bucket", {"le": _format_float(ub)}, float(cum)))
        out.append(("_bucket", {"le": "+Inf"}, float(self.count)))
        out.append(("_sum", {}, self.total))
        out.append(("_count", {}, float(self.count)))
        return out


def _format_float(x: float) -> str:
    s = repr(float(x))
    return s[:-2] if s.endswith(".0") else s


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def label_series(families: List[Dict], labels: Dict[str, str]) -> List[Dict]:
    """Return a deep-enough copy of ``families`` with ``labels`` merged
    into every sample (the Router uses this to add ``replica="N"``)."""
    out = []
    for fam in families:
        samples = [(suffix, {**labels, **lbls}, value)
                   for suffix, lbls, value in fam["samples"]]
        out.append({**fam, "samples": samples})
    return out


def merge_series(*family_lists: List[Dict]) -> List[Dict]:
    """Merge family lists by metric name, concatenating samples — the
    per-replica series of one family land under one HELP/TYPE header."""
    by_name: Dict[str, Dict] = {}
    order: List[str] = []
    for fams in family_lists:
        for fam in fams:
            have = by_name.get(fam["name"])
            if have is None:
                by_name[fam["name"]] = {**fam,
                                        "samples": list(fam["samples"])}
                order.append(fam["name"])
            else:
                have["samples"].extend(fam["samples"])
    return [by_name[n] for n in order]


def render_prometheus(families: List[Dict]) -> str:
    """Render metric families as Prometheus text exposition format 0.0.4."""
    lines: List[str] = []
    for fam in families:
        lines.append(f"# HELP {fam['name']} {fam['help']}")
        lines.append(f"# TYPE {fam['name']} {fam['type']}")
        for suffix, labels, value in fam["samples"]:
            name = fam["name"] + suffix
            if labels:
                lbl = ",".join(f'{k}="{_escape_label(str(v))}"'
                               for k, v in sorted(labels.items()))
                name = f"{name}{{{lbl}}}"
            lines.append(f"{name} {_format_float(float(value))}")
    return "\n".join(lines) + "\n"


class ServingMetrics:
    """Aggregates one engine's request/step observations.

    Latency samples are wall-clock seconds; throughput is generated tokens
    over the span from the first observation to the latest one.
    ``profiler`` is only remembered (callers that swap in a fresh registry
    pass the engine's): counters are not mirrored into it.
    """

    def __init__(self, profiler: Optional[Profiler] = None, *,
                 slo_ttft_s: Optional[float] = None,
                 slo_stall_s: Optional[float] = None,
                 reservoir_size: int = RESERVOIR_SIZE):
        self.profiler = profiler
        # SLO targets for goodput accounting (None = no SLO configured)
        self.slo_ttft_s = slo_ttft_s
        self.slo_stall_s = slo_stall_s

        def res(name: str) -> Reservoir:
            return Reservoir(name, cap=reservoir_size)

        self.ttft_s = res("ttft_s")
        self.ttft_under_load_s = res("ttft_under_load_s")
        self.token_latency_s = res("token_latency_s")
        self.decode_stall_s = res("decode_stall_s")
        self.host_gap_s = res("host_gap_s")
        self.build_s = res("build_s")
        self.commit_s = res("commit_s")
        self.put_s = res("put_s")
        self.launch_s = res("launch_s")
        self.emit_delay_s = res("emit_delay_s")
        self.step_latency_s = res("step_latency_s")
        self.queue_wait_s = res("queue_wait_s")
        self.queue_depth = res("queue_depth")
        self.pool_occupancy = res("pool_occupancy")
        self.batch_fill = res("batch_fill")
        self.mixed_step_fill = res("mixed_step_fill")
        self.finished_ttft_s = res("finished_ttft_s")  # TTFT of *finished*
        #: cumulative sum of every value ever ticked, by tick key — the
        #: counter surface behind the Prometheus exposition
        self.counters: Dict[str, float] = {}
        #: fixed-bucket histograms for the EXPOSITION "histogram" families
        self.histograms: Dict[str, Histogram] = {
            key: Histogram() for key, (_, mtype, _, _) in EXPOSITION.items()
            if mtype == "histogram"}
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        # prefix cache: admission-time lookups against the block index
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_total = 0     # prompt tokens covered by lookups
        self.prefill_tokens_saved = 0    # of those, served from cached KV
        self.prefix_cows = 0             # private copies at full-cover hits
        self.decode_tokens = 0
        # speculative decoding: drafted vs verifier-accepted candidate
        # tokens, and committed tokens (accepted + the bonus sample) per
        # decode-row step — the headline accepted-tokens-per-step number
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_committed_tokens = 0
        self.spec_row_steps = 0
        self.preemptions = 0
        self.preemptions_by_request: Dict[int, int] = {}
        self.finished = 0
        self.rejected = 0
        self.cancelled = 0
        self.timed_out = 0
        self.failed = 0
        self.step_retries = 0
        self.steps = 0
        # overlapped loop: speculatively dispatched steps torn down because
        # step N's outcome invalidated the predicted row set
        self.overlap_rebuilds = 0
        # ... and those adopted as dispatched, over every step committed;
        # each time ``try_speculate`` had a step in flight and depth to
        # spare and dispatched nothing, the ONE reason why, by name
        self.adopted_steps = 0
        # ... by the kind of the step adopted ("mixed": it pushes a prompt
        # chunk; "decode")
        self.adopted_by_kind: Dict[str, int] = dict.fromkeys(
            ("mixed", "decode"), 0)
        self.committed_steps = 0
        self.step_latency_max_s = 0.0
        # the front end's empty stdin polls: how far past their timeout
        self.front_late_max_s = 0.0
        self.speculate_refusals: Dict[str, int] = dict.fromkeys(
            ("mixed_step", "row_ends", "admission", "pool", "row_condition",
             "other"), 0)
        # a windowed model (EVA: exact window beside chunk summaries): the
        # share of the window's exact positions in use, a row and a step;
        # the pool's rows that hold summaries; windows ended
        self.eva_window_fill_sum = 0.0      # over eva_row_steps
        self.eva_row_steps = 0
        self.eva_summary_rows_max = 0.0
        self.eva_windows_rolled = 0
        # a model of window layers beside global ones (two page groups):
        # the share of the sliding window each row's context fills, a row
        # and a step; the window group's blocks in use over the pool's; the
        # blocks its rows gave back from behind their windows
        self.win_fill_sum = 0.0             # over win_row_steps
        self.win_row_steps = 0
        self.win_pool_occupancy_max = 0.0
        self.win_pages_released = 0
        # a model with state slots (kv_pool: State slots)
        self.state_steps = 0
        self.state_slots_occupancy_max = 0.0
        self.state_bytes = 0
        self.state_snapshots = 0
        self.state_restores = 0
        self.state_replayed_tokens = 0
        self.attn_fetch_fill_sum = 0.0      # over attn_fetch_row_steps
        self.attn_fetch_row_steps = 0
        # paged launches wider than one query tile: the tiles the kernel
        # computed (a short row's first alone) and the tiles held
        self.attn_query_tiles_computed = 0
        self.attn_query_tiles_held = 0
        # step programs dispatched, and those with a row that asks for a
        # draw: the others run the sampler's argmax alone
        self.dispatched_steps = 0
        self.sampled_steps = 0
        # a model with an expert layer of which this chip holds a share
        # (nn.moe.ExpertShare): assignments that fell on held experts and
        # all assignments; over layer-steps, the share of held experts with
        # a token and the busiest held expert's load over the mean
        self.expert_held_assignments = 0
        self.expert_assignments = 0
        self.expert_layer_steps = 0
        self.expert_hit_sum = 0.0
        self.expert_imbalance_sum = 0.0
        # ... and zero-compute experts: a live token's picks that fell on
        # them; the tokens a layer routed; over layer-steps, the most real
        # experts a token picked over the mean ("compute a token varies")
        self.expert_zero_picks = 0
        self.expert_layer_tokens = 0
        self.expert_picks_spread_sum = 0.0
        # runtime-resilience counters (supervisor / overload degradation)
        self.shed = 0                 # queued requests displaced by priority
        self.engine_restarts = 0      # supervisor-driven engine recoveries
        self.drain_duration_s = 0.0   # wall time of the last graceful drain
        self.publish_suspended = 0    # prefix publishes skipped under pressure
        # crash-migration counters (in-flight survival + router failover)
        self.migrated_requests = 0       # re-admissions after a crash/failover
        self.migration_resume_tokens = 0  # tokens re-prefilled by migrations
        self.router_retries = 0          # router-level dispatch retries
        # gray-failure tolerance counters (health-scored routing / hedging)
        self.hedges_fired = 0         # duplicates dispatched past the threshold
        self.hedges_won = 0           # races the duplicate stream won
        self.hedges_cancelled = 0     # losing streams cancelled/discarded
        self.degraded_ejections = 0   # replicas ejected from placement
        self.proactive_migrations = 0  # streams pulled off degraded replicas
        # host-KV-tier counters (elastic fleet)
        self.tier_hits = 0            # blocks re-admitted from the host tier
        self.tier_corrupt = 0         # entries dropped on digest mismatch
        # disaggregated-serving counters (cross-replica KV handoff)
        self.handoff_exported_blocks = 0  # blocks serialized for shipment
        self.handoff_adopted_blocks = 0   # wire blocks digest-verified in
        self.handoff_corrupt = 0          # wire blocks failing their digest
        self.boundary_handoffs = 0    # prefill->decode replica handoffs
        self.handoff_fallbacks = 0    # handoffs degraded to recompute-resume
        self.fleet_prefix_pulls = 0   # peer-sourced prefix admissions
        self._t_created = time.perf_counter()
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    # -- observations ---------------------------------------------------------

    def _mark(self) -> float:
        now = time.perf_counter()
        if self._t_first is None:
            self._t_first = now
        self._t_last = now
        return now

    def _tick(self, metric: str, value: float) -> None:
        self.counters[metric] = self.counters.get(metric, 0.0) + value
        hist = self.histograms.get(metric)
        if hist is not None:
            hist.observe(value)

    def observe_ttft(self, seconds: float, under_load: bool = False) -> None:
        """``under_load`` marks a first token produced while OTHER requests
        were decoding in the same step — the TTFT population chunked prefill
        exists to protect (an unloaded TTFT can't stall anyone)."""
        self._mark()
        self.ttft_s.append(seconds)
        if under_load:
            self.ttft_under_load_s.append(seconds)
        self._tick("serve.ttft_s", seconds)

    def observe_prefill_chunk(self, num_tokens: int) -> None:
        """One prompt chunk pushed inside a mixed step."""
        self._mark()
        self.prefill_chunks += 1
        self.prefill_tokens += num_tokens
        self._tick("serve.prefill_chunks", 1)

    def observe_prefix_lookup(self, tokens_saved: int, total: int) -> None:
        """One admission-time prefix-cache probe over a ``total``-token
        prompt, of which ``tokens_saved`` positions matched cached KV and
        will never be prefilled (0 on a miss)."""
        self._mark()
        self.prefix_lookups += 1
        self.prefix_tokens_total += total
        if tokens_saved > 0:
            self.prefix_hits += 1
            self.prefill_tokens_saved += tokens_saved
        self._tick("serve.prefix_tokens_saved", tokens_saved)

    def observe_prefix_cow(self) -> None:
        """A fully-cached prompt took a private copy of its last matched
        block (copy-on-write before the recomputed-token KV write)."""
        self.prefix_cows += 1
        self._tick("serve.prefix_cows", 1)

    def observe_mixed_step(self, live_tokens: int, width: int) -> None:
        """Packing efficiency of one mixed prefill+decode step: live tokens
        (decode rows + live chunk tokens) over the compiled B*Q capacity."""
        if width:
            self.mixed_step_fill.append(live_tokens / width)
            self._tick("serve.mixed_step_fill", live_tokens / width)

    def observe_decode_stall(self, seconds: float) -> None:
        """Wall gap between consecutive steps that emitted decode-phase
        tokens — what chunked prefill bounds."""
        self.decode_stall_s.append(seconds)
        self._tick("serve.decode_stall_s", seconds)

    def observe_host_gap(self, seconds: float) -> None:
        """Wall gap between a step's bundle fetch and the next dispatch — the
        window where the device sits idle on host bookkeeping. The overlapped
        loop exists to drive this toward zero."""
        self.host_gap_s.append(seconds)
        self._tick("serve.host_gap_s", seconds)

    def observe_build(self, seconds: float) -> None:
        """One ``serve.build`` phase: ``begin_step`` up to its first launch
        (deadline expiry, scheduling, admissions, input staging)."""
        self.build_s.append(seconds)
        self._tick("serve.build_s", seconds)

    def observe_commit(self, seconds: float) -> None:
        """One ``serve.commit`` phase: ``finish_step`` after its fetch (pool
        and scheduler state, stop checks, event buckets)."""
        self.commit_s.append(seconds)
        self._tick("serve.commit_s", seconds)

    def observe_put(self, seconds: float) -> None:
        """The ``serve.put`` spans of one launch: every ``device_put`` of the
        step's host arrays (a mixed step's token matrix, staged inside
        ``serve.build``, included)."""
        self.put_s.append(seconds)
        self._tick("serve.put_s", seconds)

    def observe_launch(self, seconds: float) -> None:
        """One ``serve.launch``: the jitted call alone, from its arguments'
        flattening to the asynchronous enqueue's return."""
        self.launch_s.append(seconds)
        self._tick("serve.launch_s", seconds)

    def observe_front_late(self, seconds: float) -> None:
        """One stdin poll of the front end that came back empty, by how much
        it overran the time it asked for. That thread touches no device and
        sleeps in ``select``: what it loses, the machine took. Called from
        the front end's thread."""
        self.front_late_max_s = max(self.front_late_max_s, seconds)
        self._tick("serve.front_late_s", seconds)

    def observe_emit_delay(self, seconds: float) -> None:
        """One ``token`` event's wait between its step's commit and the
        front end's flush that wrote it out: the front-end layer seen from
        inside (half the stdin poll, on average, while a step is long).
        Called from the front end's thread."""
        self.emit_delay_s.append(seconds)
        self._tick("serve.emit_delay_s", seconds)

    def observe_overlap_rebuild(self) -> None:
        """A speculatively dispatched step N+1 was rolled back because step
        N's committed outcome invalidated its predicted row set."""
        self.overlap_rebuilds += 1
        self._tick("serve.overlap_rebuild", 1)

    def observe_adopted_step(self, kind: str) -> None:
        """A step dispatched behind its predecessor was adopted as the next
        step: it never ran ``begin_step``, and its host gap is zero.
        ``kind``: "mixed" (it pushes a prompt chunk) or "decode"."""
        self.adopted_steps += 1
        self.adopted_by_kind[kind] += 1
        self._tick("serve.adopted_step", 1)
        if kind == "mixed":
            self._tick("serve.adopted_mixed_step", 1)
        else:
            self._tick("serve.adopted_decode_step", 1)

    def observe_speculate_refusal(self, reason: str) -> None:
        """``try_speculate`` had a step in flight and depth to spare and
        dispatched nothing. ``reason`` is one of a closed list:
        ``mixed_step`` (a row pushing its prompt is left out of the step in
        flight or of the next: the token budget gave it no chunk),
        ``row_ends`` (a row's last token comes before the step would run),
        ``admission`` (the scheduler would admit the head of the queue at
        that step), ``pool`` (the rows' next pages do not fit without a
        preemption), ``row_condition`` (a stop token or a deadline on a row,
        a window's end before the step, a chunk whose commit gives a
        window's pages back), ``other`` (a drafter or a fault plan, a row
        that left in flight, a dispatch that failed)."""
        self.speculate_refusals[reason] += 1

    def observe_eva_step(self, window_fills, summary_share: float) -> None:
        """One step of a windowed model: ``window_fills`` the share of the
        window's exact positions each of its rows attends over,
        ``summary_share`` the summary rows held by all running requests over
        the rows of the whole pool."""
        self.eva_window_fill_sum += sum(window_fills)
        self.eva_row_steps += len(window_fills)
        self.eva_summary_rows_max = max(self.eva_summary_rows_max,
                                        summary_share)

    def observe_window_step(self, fills, occupancy: float) -> None:
        """One step of a model of two page groups: ``fills`` each row's
        ``min(context, window) / window``, ``occupancy`` the window group's
        blocks held by all running requests over the pool's."""
        self.win_fill_sum += sum(fills)
        self.win_row_steps += len(fills)
        self.win_pool_occupancy_max = max(self.win_pool_occupancy_max,
                                          occupancy)

    def observe_state_step(self, occupancy: float, snapshots: int,
                           nbytes: int) -> None:
        """One dispatched step of a model with state slots: the share of
        the slots held, how many of its rows kept the state they read in a
        snapshot, and the bytes of the pool's four state arrays."""
        self.state_steps += 1
        self.state_bytes = int(nbytes)
        self.state_slots_occupancy_max = max(self.state_slots_occupancy_max,
                                             occupancy)
        self.state_snapshots += int(snapshots)

    def observe_state_restore(self, rows: int, tokens: int) -> None:
        """A rolled-back chain put ``rows`` rows' states back; ``tokens``
        committed tokens lie behind the restored positions and are pushed
        again."""
        self.state_restores += int(rows)
        self.state_replayed_tokens += int(tokens)

    def observe_window_release(self, blocks: int) -> None:
        """A row gave ``blocks`` window-group blocks back: the pages now
        wholly behind its window, in every window layer."""
        self.win_pages_released += int(blocks)

    def observe_attn_fetch(self, fills) -> None:
        """One paged step: ``fills`` each live row's live pages over the
        page slots of the groups the paged kernel fetches for them
        (``paged_attention.fetch_group``)."""
        self.attn_fetch_fill_sum += float(sum(fills))
        self.attn_fetch_row_steps += len(fills)

    def observe_attn_query_tiles(self, computed: int, held: int) -> None:
        """One paged step wider than one query tile
        (``paged_attention.query_tile``): ``computed`` the tiles the kernel
        computes (``paged_attention.query_tiles_computed``, summed over the
        launch's rows) of the ``held`` tiles the launch is wide."""
        self.attn_query_tiles_computed += int(computed)
        self.attn_query_tiles_held += int(held)

    def observe_step_dispatch(self, sampled_rows: int) -> None:
        """One step program dispatched, ``sampled_rows`` of whose rows have
        a temperature above 0."""
        self.dispatched_steps += 1
        self.sampled_steps += int(sampled_rows > 0)

    def observe_experts(self, tokens: int, top_k: int, counts,
                        zero=None) -> None:
        """One step of a model with an expert layer, ``tokens`` live tokens
        of ``top_k`` picks each: ``counts`` (layers, held) the assignments
        each held expert took in each layer, out of all the step's (live
        token, expert) pairs a layer, those that fell on experts held
        elsewhere too. ``zero`` (layers, 2), of a model with zero-compute
        experts: the picks that fell on them, and the most REAL experts any
        one token picked."""
        layers = counts.shape[0]
        assignments = tokens * top_k
        if zero is not None and tokens:
            self.expert_zero_picks += int(zero[:, 0].sum())
            self.expert_layer_tokens += tokens * layers
            mean = (assignments - zero[:, 0]) / tokens
            self.expert_picks_spread_sum += float(
                (zero[:, 1] / (mean + (mean == 0))).sum())
        self.expert_held_assignments += int(counts.sum())
        self.expert_assignments += assignments * layers
        self.expert_layer_steps += layers
        self.expert_hit_sum += float((counts > 0).mean(axis=1).sum())
        mean = counts.mean(axis=1)
        # a layer no held expert of which took a token reads 0, not 0 / 0
        self.expert_imbalance_sum += float(
            (counts.max(axis=1) / (mean + (mean == 0))).sum())

    def observe_eva_roll(self) -> None:
        """A request's window ended: its exact pages went back to the pool."""
        self.eva_windows_rolled += 1

    def observe_decode(self, num_tokens: int, seconds: float,
                       batch_width: int) -> None:
        """One decode step producing ``num_tokens`` live tokens out of a
        compiled batch ``batch_width`` wide (fill ratio = padding waste)."""
        self._mark()
        self.decode_tokens += num_tokens
        self.steps += 1
        if num_tokens:
            # every live request received exactly one token this step, so the
            # step wall time IS the per-token latency each of them experienced
            self.token_latency_s.append(seconds)
            self._tick("serve.token_latency_s", seconds)
        if batch_width:
            self.batch_fill.append(num_tokens / batch_width)
        self._tick("serve.decode_s", seconds)

    def observe_step_latency(self, seconds: float) -> None:
        """Wall time of one whole engine step (any kind) — the flight
        recorder's and the step-latency histogram's shared source."""
        self.committed_steps += 1
        self.step_latency_s.append(seconds)
        self.step_latency_max_s = max(self.step_latency_max_s, seconds)
        self._tick("serve.step_latency_s", seconds)

    def observe_queue_wait(self, seconds: float) -> None:
        """Continuous QUEUED time ended by one admission (re-admissions
        after preemption/migration observe their own wait)."""
        self.queue_wait_s.append(seconds)
        self._tick("serve.queue_wait_s", seconds)

    def observe_spec(self, drafted: int, accepted: int, committed: int,
                     rows: int = 1) -> None:
        """Speculative-decoding outcome for ``rows`` decode-row steps:
        ``drafted`` candidate tokens proposed, ``accepted`` of them verified,
        ``committed`` tokens actually emitted (accepted prefix + the bonus
        sample, clipped by stop-token/length finishes). Rows that drafted
        nothing still count — a drafter that never fires must show a
        mean-accepted-per-step of ~1, not a flattering NaN."""
        self._mark()
        self.spec_draft_tokens += drafted
        self.spec_accepted_tokens += accepted
        self.spec_committed_tokens += committed
        self.spec_row_steps += rows
        self._tick("serve.spec_accepted", accepted)

    def observe_gauges(self, queue_depth: int, pool_occupancy: float,
                       kv_bytes_per_token: float = 0.0,
                       tp_degree: float = 1.0,
                       sp_degree: float = 1.0,
                       tier_blocks: int = 0,
                       tier_bytes: float = 0.0) -> None:
        self.queue_depth.append(queue_depth)
        self.pool_occupancy.append(pool_occupancy)
        self._last_queue_depth = queue_depth
        self._last_pool_occupancy = pool_occupancy
        self._last_kv_bytes_per_token = kv_bytes_per_token
        self._last_tp_degree = tp_degree
        self._last_sp_degree = sp_degree
        self._last_tier_blocks = tier_blocks
        self._last_tier_bytes = tier_bytes

    def observe_replicas(self, n: int) -> None:
        """Active replica count after a fleet change (scale-up/down, death,
        readmit) — the ``tnn_serve_replicas`` gauge's source."""
        self._last_replicas = n

    def observe_tier_hit(self, blocks: int = 1) -> None:
        """``blocks`` KV blocks re-admitted from the host tier (each one a
        digest-verified device_put instead of a recomputed prefill)."""
        self.tier_hits += blocks
        self._tick("serve.tier_hits", blocks)

    def observe_tier_corrupt(self) -> None:
        """A host-tier entry failed its integrity digest at readmit and was
        dropped — the lookup degraded to an uncached miss."""
        self.tier_corrupt += 1
        self._tick("serve.tier_corrupt", 1)

    def observe_handoff_export(self, blocks: int) -> None:
        """``blocks`` KV blocks serialized (with chain key + digest) for
        cross-replica shipment — from device pages or host-tier staging."""
        self.handoff_exported_blocks += blocks
        self._tick("serve.handoff_exported", blocks)

    def observe_handoff_adopt(self, blocks: int) -> None:
        """``blocks`` wire KV blocks adopted after digest verification —
        prefill work the receiving replica never re-ran."""
        self.handoff_adopted_blocks += blocks
        self._tick("serve.handoff_adopted", blocks)

    def observe_handoff_corrupt(self) -> None:
        """A wire KV block failed its integrity digest at adopt and was
        dropped — the handoff degrades to recompute-resume."""
        self.handoff_corrupt += 1
        self._tick("serve.handoff_corrupt", 1)

    def observe_boundary_handoff(self) -> None:
        """One request handed prefill->decode across replicas at its
        first-token boundary."""
        self.boundary_handoffs += 1
        self._tick("serve.boundary_handoffs", 1)

    def observe_handoff_fallback(self) -> None:
        """A boundary handoff's KV shipment failed or fell short; the
        stream continued token-exact via recompute-resume."""
        self.handoff_fallbacks += 1
        self._tick("serve.handoff_fallbacks", 1)

    def observe_fleet_prefix_pull(self) -> None:
        """An admission's prefix KV was pulled from a peer replica via the
        fleet chain-key directory instead of recomputed locally."""
        self.fleet_prefix_pulls += 1
        self._tick("serve.fleet_prefix_pulls", 1)

    def observe_preemption(self, rid: Optional[int] = None) -> None:
        self.preemptions += 1
        if rid is not None:
            self.preemptions_by_request[rid] = \
                self.preemptions_by_request.get(rid, 0) + 1
        self._tick("serve.preemptions", 1)

    def observe_finish(self, ttft_s: Optional[float] = None) -> None:
        self.finished += 1
        if ttft_s is not None:
            self.finished_ttft_s.append(ttft_s)

    def observe_shed(self) -> None:
        """A queued request was displaced by a more important arrival."""
        self.shed += 1
        self._tick("serve.shed", 1)

    def observe_restart(self) -> None:
        """The supervisor reset the engine after a crash or watchdog trip."""
        self.engine_restarts += 1
        self._tick("serve.engine_restarts", 1)

    def observe_migration(self, resume_tokens: int) -> None:
        """One RUNNING request re-admitted through the resume path after an
        engine restart or replica failover; ``resume_tokens`` is the length
        of the extended prompt its next (re-)prefill must push."""
        self.migrated_requests += 1
        self.migration_resume_tokens += resume_tokens
        self._tick("serve.migrated_requests", 1)

    def observe_router_retry(self) -> None:
        """The router re-dispatched a request after a replica-level failure
        (backoff retry or mid-stream migration to another replica)."""
        self.router_retries += 1
        self._tick("serve.router_retries", 1)

    def observe_hedge_fired(self) -> None:
        """A request idled past the TTFT hedge threshold and was duplicated
        onto a second replica under a fresh epoch."""
        self.hedges_fired += 1
        self._tick("serve.hedges_fired", 1)

    def observe_hedge_won(self) -> None:
        """The duplicate stream won the hedge race (first token, or
        promotion after the primary replica died)."""
        self.hedges_won += 1
        self._tick("serve.hedges_won", 1)

    def observe_hedge_cancelled(self) -> None:
        """A hedge loser was cancelled/discarded once the race resolved."""
        self.hedges_cancelled += 1
        self._tick("serve.hedges_cancelled", 1)

    def observe_ejection(self) -> None:
        """A replica's health score stayed above the degrade threshold and
        it was ejected from placement as DEGRADED (gray failure)."""
        self.degraded_ejections += 1
        self._tick("serve.degraded_ejections", 1)

    def observe_proactive_migration(self) -> None:
        """A live stream was migrated off a degraded replica before the
        replica failed outright."""
        self.proactive_migrations += 1
        self._tick("serve.proactive_migrations", 1)

    def observe_drain(self, seconds: float) -> None:
        self.drain_duration_s = seconds
        self._tick("serve.drain_duration_s", seconds)

    def observe_publish_suspended(self) -> None:
        """A prefix-cache publish was skipped because the pool was under
        occupancy pressure (degradation mode, not an error)."""
        self.publish_suspended += 1
        self._tick("serve.publish_suspended", 1)

    def observe_rejected(self) -> None:
        self.rejected += 1
        self._tick("serve.rejected", 1)

    def observe_cancelled(self) -> None:
        self.cancelled += 1
        self._tick("serve.cancelled", 1)

    def observe_timeout(self) -> None:
        self.timed_out += 1
        self._tick("serve.timed_out", 1)

    def observe_failed(self) -> None:
        self.failed += 1
        self._tick("serve.failed", 1)

    def observe_step_retry(self) -> None:
        """A transient decode fault was retried (same key, same inputs)."""
        self.step_retries += 1
        self._tick("serve.step_retries", 1)

    # -- aggregates -----------------------------------------------------------

    @property
    def elapsed_s(self) -> float:
        if self._t_first is None or self._t_last is None:
            return 0.0
        return self._t_last - self._t_first

    @property
    def tokens_per_s(self) -> float:
        el = self.elapsed_s
        return self.decode_tokens / el if el > 0 else 0.0

    @property
    def uptime_s(self) -> float:
        return time.perf_counter() - self._t_created

    @property
    def goodput_at_slo(self) -> float:
        """Finished requests per second that met the TTFT SLO — the number a
        sustained-load harness should optimize, not raw throughput. With no
        SLO configured, every finished request counts (plain req/s)."""
        el = self.elapsed_s
        if el <= 0:
            return 0.0
        if self.slo_ttft_s is None:
            good = self.finished
        else:
            good = sum(1 for t in _finite(self.finished_ttft_s)
                       if t <= self.slo_ttft_s)
        return good / el

    @property
    def stall_slo_violations(self) -> int:
        """Decode-stall samples exceeding the stall SLO (0 when unset)."""
        if self.slo_stall_s is None:
            return 0
        return sum(1 for s in _finite(self.decode_stall_s)
                   if s > self.slo_stall_s)

    def summary(self) -> Dict[str, float]:
        """One flat dict: what ``engine.stats()`` and the ``tnn-serve``
        summary line report.

        Every aggregate is NaN-safe and defined on empty series (0.0), so a
        run with zero decode steps — e.g. every prompt fully served from the
        prefix cache and immediately finished — still summarizes cleanly.

        Prefix-cache keys:

        - ``prefill_tokens_saved``: prompt positions admitted straight from
          cached KV blocks — prefill FLOPs that never ran.
        - ``prefix_hit_rate``: ``prefill_tokens_saved`` over all prompt
          tokens that went through a cache lookup (token-weighted, so one
          long cached prompt counts for more than many short misses);
          0.0 when the cache is off or no lookups happened.
        """
        def ms(x):
            return x * 1e3

        out = {
            "requests_finished": self.finished,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "steps": self.steps,
            "preemptions": self.preemptions,
            "preemptions_max_per_request": max(
                self.preemptions_by_request.values(), default=0),
            "rejected": self.rejected,
            "cancelled": self.cancelled,
            "timed_out": self.timed_out,
            "failed": self.failed,
            "step_retries": self.step_retries,
            "uptime_s": self.uptime_s,
            "engine_restarts": self.engine_restarts,
            "drain_duration_s": self.drain_duration_s,
            "shed_requests": self.shed,
            "publish_suspended": self.publish_suspended,
            "migrated_requests": self.migrated_requests,
            "migration_resume_tokens": self.migration_resume_tokens,
            "router_retries": self.router_retries,
            "hedges_fired": self.hedges_fired,
            "hedges_won": self.hedges_won,
            "hedges_cancelled": self.hedges_cancelled,
            "degraded_ejections": self.degraded_ejections,
            "proactive_migrations": self.proactive_migrations,
            "goodput_at_slo": self.goodput_at_slo,
            "stall_slo_violations": self.stall_slo_violations,
            "tok_per_s": self.tokens_per_s,
            "spec_draft_tokens": self.spec_draft_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_acceptance_rate": (self.spec_accepted_tokens
                                     / self.spec_draft_tokens)
            if self.spec_draft_tokens else 0.0,
            "mean_accepted_per_step": (self.spec_committed_tokens
                                       / self.spec_row_steps)
            if self.spec_row_steps else 0.0,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_cows": self.prefix_cows,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefix_hit_rate": (self.prefill_tokens_saved
                                / self.prefix_tokens_total)
            if self.prefix_tokens_total else 0.0,
            "ttft_ms_mean": ms(_mean(self.ttft_s)),
            "ttft_ms_p50": ms(_percentile(self.ttft_s, 50)),
            "ttft_ms_p95": ms(_percentile(self.ttft_s, 95)),
            "ttft_ms_p99": ms(_percentile(self.ttft_s, 99)),
            "ttft_under_load_ms_p50": ms(_percentile(self.ttft_under_load_s,
                                                     50)),
            "ttft_under_load_ms_p99": ms(_percentile(self.ttft_under_load_s,
                                                     99)),
            "token_latency_ms_p50": ms(_percentile(self.token_latency_s, 50)),
            "token_latency_ms_p95": ms(_percentile(self.token_latency_s, 95)),
            "token_latency_ms_p99": ms(_percentile(self.token_latency_s, 99)),
            "decode_stall_ms_p50": ms(_percentile(self.decode_stall_s, 50)),
            "decode_stall_ms_p99": ms(_percentile(self.decode_stall_s, 99)),
            "decode_stall_ms_max": ms(_max(self.decode_stall_s)),
            "host_gap_ms_mean": ms(_mean(self.host_gap_s)),
            "host_gap_ms_p50": ms(_percentile(self.host_gap_s, 50)),
            "host_gap_ms_p99": ms(_percentile(self.host_gap_s, 99)),
            "build_ms_p50": ms(_percentile(self.build_s, 50)),
            "build_ms_p99": ms(_percentile(self.build_s, 99)),
            "commit_ms_p50": ms(_percentile(self.commit_s, 50)),
            "commit_ms_p99": ms(_percentile(self.commit_s, 99)),
            "put_ms_p50": ms(_percentile(self.put_s, 50)),
            "launch_ms_p50": ms(_percentile(self.launch_s, 50)),
            "emit_delay_ms_p50": ms(_percentile(self.emit_delay_s, 50)),
            "emit_delay_ms_p99": ms(_percentile(self.emit_delay_s, 99)),
            "front_late_ms_total": ms(self.counters.get(
                "serve.front_late_s", 0.0)),
            "front_late_ms_max": ms(self.front_late_max_s),
            "overlap_rebuilds": self.overlap_rebuilds,
            "adopted_step_share": (self.adopted_steps / self.committed_steps)
            if self.committed_steps else 0.0,
            "adopted_mixed_steps": self.adopted_by_kind["mixed"],
            "adopted_decode_steps": self.adopted_by_kind["decode"],
            **{f"speculate_refused_{reason}": n
               for reason, n in self.speculate_refusals.items()},
            "speculate_refused_mixed_step_share": (
                self.speculate_refusals["mixed_step"] / self.committed_steps)
            if self.committed_steps else 0.0,
            "sampled_step_share": (self.sampled_steps / self.dispatched_steps)
            if self.dispatched_steps else 0.0,
            "step_latency_ms_p50": ms(_percentile(self.step_latency_s, 50)),
            "step_latency_ms_p99": ms(_percentile(self.step_latency_s, 99)),
            # exact over every step, not over the reservoir's sample
            "step_latency_ms_mean": ms(
                self.counters.get("serve.step_latency_s", 0.0)
                / self.committed_steps) if self.committed_steps else 0.0,
            "step_latency_ms_max": ms(self.step_latency_max_s),
            "queue_wait_ms_p50": ms(_percentile(self.queue_wait_s, 50)),
            "queue_wait_ms_p99": ms(_percentile(self.queue_wait_s, 99)),
            "prefill_chunks": self.prefill_chunks,
            "queue_depth_max": max(self.queue_depth, default=0),
            "pool_occupancy_max": _max(self.pool_occupancy),
            "batch_fill_mean": _mean(self.batch_fill),
            "mixed_step_fill_mean": _mean(self.mixed_step_fill),
            "kv_bytes_per_token": getattr(self, "_last_kv_bytes_per_token",
                                          0.0),
            "tp_degree": getattr(self, "_last_tp_degree", 1.0),
            "sp_degree": getattr(self, "_last_sp_degree", 1.0),
            "tier_hits": self.tier_hits,
            "tier_corrupt": self.tier_corrupt,
            "handoff_exported_blocks": self.handoff_exported_blocks,
            "handoff_adopted_blocks": self.handoff_adopted_blocks,
            "handoff_corrupt": self.handoff_corrupt,
            "boundary_handoffs": self.boundary_handoffs,
            "handoff_fallbacks": self.handoff_fallbacks,
            "fleet_prefix_pulls": self.fleet_prefix_pulls,
            "tier_blocks": getattr(self, "_last_tier_blocks", 0),
            "tier_bytes": getattr(self, "_last_tier_bytes", 0.0),
            "replicas": getattr(self, "_last_replicas", 0.0),
        }
        if self.attn_fetch_row_steps:
            # only the paged decode path fetches pages in groups
            out["attn_fetch_fill_mean"] = (self.attn_fetch_fill_sum
                                           / self.attn_fetch_row_steps)
        if self.attn_query_tiles_held:
            # only a paged launch wider than one query tile has tiles to
            # leave out: 1.0 = every row was longer than a tile
            out["attn_query_tile_share"] = (self.attn_query_tiles_computed
                                            / self.attn_query_tiles_held)
        if self.eva_row_steps:
            # only a windowed model has these: a reader of a model with
            # every position exact finds nothing, not a zero
            out.update(
                eva_window_fill_mean=(self.eva_window_fill_sum
                                      / self.eva_row_steps),
                eva_summary_rows_max=self.eva_summary_rows_max,
                eva_windows_rolled=self.eva_windows_rolled)
        if self.win_row_steps:
            # only a model of two page groups has these
            out.update(
                win_fill_mean=self.win_fill_sum / self.win_row_steps,
                win_pool_occupancy_max=self.win_pool_occupancy_max,
                win_pages_released=self.win_pages_released)
        if self.state_steps:
            # only a model with state slots has these
            out.update(
                state_slots_occupancy_max=self.state_slots_occupancy_max,
                state_bytes=self.state_bytes,
                state_snapshots=self.state_snapshots,
                state_restores=self.state_restores,
                state_replayed_tokens=self.state_replayed_tokens)
        if self.expert_layer_steps:
            # only a model with an expert layer has these
            out.update(
                expert_held_share=(self.expert_held_assignments
                                   / max(self.expert_assignments, 1)),
                experts_hit_share=(self.expert_hit_sum
                                   / self.expert_layer_steps),
                expert_load_max_over_mean=(self.expert_imbalance_sum
                                           / self.expert_layer_steps))
        if self.expert_layer_tokens:
            # ... and only one with zero-compute experts has these
            real = self.expert_assignments - self.expert_zero_picks
            out.update(
                zero_pick_share=(self.expert_zero_picks
                                 / max(self.expert_assignments, 1)),
                ffn_picks_per_token_mean=real / self.expert_layer_tokens,
                ffn_picks_max_over_mean=(self.expert_picks_spread_sum
                                         / self.expert_layer_steps))
        return out

    # -- Prometheus exposition ------------------------------------------------

    def prometheus_series(self) -> List[Dict]:
        """Metric families for ``render_prometheus``: every EXPOSITION
        entry (counters render the cumulative ticked sum, histograms their
        fixed buckets), the direct request/token counters, and the live
        gauges. Families render even before their first observation, so
        the scrape surface is stable from the first request."""
        families: List[Dict] = []
        for key, (name, mtype, help_, summary_key) in EXPOSITION.items():
            if mtype == "histogram":
                samples = self.histograms[key].samples()
            elif mtype == "gauge":
                # gauges render the last observed value (stored by
                # observe_gauges under the summary key), not a ticked sum
                samples = [("", {}, float(getattr(
                    self, "_last_" + summary_key, 0.0)))]
            else:
                samples = [("", {}, self.counters.get(key, 0.0))]
            families.append({"name": name, "type": mtype, "help": help_,
                             "samples": samples})
        for attr, name, mtype, help_ in _DIRECT_FAMILIES:
            families.append({"name": name, "type": mtype, "help": help_,
                             "samples": [("", {}, float(getattr(self,
                                                                attr)))]})
        families.append({
            "name": "tnn_serve_speculate_refusals_total", "type": "counter",
            "help": "try_speculate calls with a step in flight and depth to "
                    "spare that dispatched nothing, by reason",
            "samples": [("", {"reason": reason}, float(n))
                        for reason, n in self.speculate_refusals.items()]})
        families.append({
            "name": "tnn_serve_queue_depth", "type": "gauge",
            "help": "Waiting requests at the last engine step",
            "samples": [("", {}, float(getattr(self, "_last_queue_depth",
                                               0)))]})
        families.append({
            "name": "tnn_serve_pool_occupancy", "type": "gauge",
            "help": "KV pool block occupancy ratio at the last engine step",
            "samples": [("", {}, float(getattr(self, "_last_pool_occupancy",
                                               0.0)))]})
        families.append({
            "name": "tnn_serve_uptime_seconds", "type": "gauge",
            "help": "Seconds since this metrics registry was created",
            "samples": [("", {}, self.uptime_s)]})
        return families
