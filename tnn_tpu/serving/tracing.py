"""Request-scoped tracing + crash flight recorder for the serving stack.

Two small, host-side-only observability primitives (neither ever touches a
device array, so the ``TNN_DEBUG_SYNC`` transfer guard and the
host-sync-in-step-path lint stay clean with tracing enabled):

- ``Tracer`` — the one span/instant API of the serving stack, with two
  sinks. (1) The JAX profiler's trace, always: every span enters a
  ``jax.profiler.TraceAnnotation`` (``profiling.span``) whose attributes are
  the event's stats, so whenever somebody records a profile
  (``jax.profiler.start_trace``, the benchmark's ``--trace 1``) the engine's
  phases sit on one clock with the device ops. Outside a session that costs
  an atomic load: no flag guards it. (2) A ``profiling.Profiler``, only when
  one is wired (``trace=True``): there the attributes ``(trace_id, rid,
  step_seq)`` are encoded into the event NAME so
  ``Profiler.to_chrome_trace`` yields one Perfetto view across
  router → replicas → engine steps (one track per profiler ``source``).
  Tracing on must change nothing (on ≡ off token-exact is a standing gate).

- ``FlightRecorder`` — a bounded ring buffer of recent engine step
  records (step kind + compile key, batch rids, fill, pool occupancy,
  step latency, faults fired), owned by the supervisor and dumped as
  JSONL on crash, watchdog trip, restart-budget exhaustion, and drain.
  The post-mortem artifact for every failure path the chaos suite
  exercises: the final record of a crash dump identifies the step (and
  batch) that died.

Trace ids are deterministic (caller-assigned, derived from request ids) —
no randomness, so traced replays stay reproducible.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from ..profiling.profiler import EventType, Profiler, span


def span_name(base: str, **attrs: Any) -> str:
    """Encode span attributes into the event name (``base k=v k=v``) for the
    ``Profiler`` sink, whose event model is (type, start, end, name, source):
    flat names keep the span usable by both ``to_chrome_trace`` and
    ``tools/visualize_profiler``. The profiler-trace sink carries the same
    attributes as stats and leaves the name alone.
    """
    if not attrs:
        return base
    parts = [f"{k}={v}" for k, v in attrs.items() if v is not None]
    return base + (" " + " ".join(parts) if parts else "")


class _Span:
    """One open span in both sinks; ``Tracer.span`` returns it entered-able
    (``with``) and the engine's phases close it non-lexically (``close``)."""

    __slots__ = ("_profiler", "_type", "_name", "_ann", "_t0")

    def __init__(self, profiler, type, name, ann):
        self._profiler, self._type, self._name = profiler, type, name
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self._profiler.add_event(self._type, self._t0, time.perf_counter(),
                                 self._name)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Span/instant recorder: always into the JAX profiler's trace, and into
    a ``Profiler`` when one is wired. All methods are safe from any thread —
    the profiler locks, and a ``TraceAnnotation`` is per-thread."""

    def __init__(self, profiler: Optional[Profiler] = None):
        self.profiler = profiler

    @property
    def enabled(self) -> bool:
        """Whether the ``Profiler`` sink is wired (the annotation sink is
        always on and needs no guard)."""
        return self.profiler is not None

    def span(self, base: str, type: EventType = EventType.OTHER,
             **attrs: Any):
        """Timed span over a ``with`` body: ``base`` with ``attrs`` as stats
        in the profiler trace, ``base k=v ...`` in the ``Profiler``."""
        ann = span(base, **attrs)
        if self.profiler is None:
            return ann
        return _Span(self.profiler, type, span_name(base, **attrs), ann)

    def instant(self, base: str, type: EventType = EventType.OTHER,
                **attrs: Any) -> None:
        """Zero-duration marker (submit, retry, preemption, publish...)."""
        with span(base, **{k: v for k, v in attrs.items()
                           if v is not None}):
            pass
        if self.profiler is not None:
            now = time.perf_counter()
            self.profiler.add_event(type, now, now, span_name(base, **attrs))


class FlightRecorder:
    """Bounded ring buffer of step records with JSONL dumps.

    Records are plain dicts (one engine step each — see
    ``InferenceEngine.last_step_record``). ``dump`` writes a meta header
    line (reason, capacity, counts) followed by the retained records in
    step order; the last line of a crash dump is the crashing step.
    """

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError("flight recorder capacity must be >= 1")
        self.capacity = int(capacity)
        self._records: Deque[Dict[str, Any]] = deque(maxlen=self.capacity)
        self._total = 0              # records ever seen (ring may drop old)
        self._dumps = 0
        self._lock = threading.Lock()

    def record(self, rec: Optional[Dict[str, Any]]) -> None:
        if rec is None:
            return
        with self._lock:
            self._total += 1
            self._records.append(dict(rec))

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._records]

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def dump(self, path: str, reason: str,
             extra: Optional[Dict[str, Any]] = None) -> str:
        """Write the retained records as JSONL; returns ``path``."""
        with self._lock:
            records = [dict(r) for r in self._records]
            total = self._total
            self._dumps += 1
        meta: Dict[str, Any] = {
            "kind": "flight_recorder_meta",
            "reason": reason,
            "capacity": self.capacity,
            "records": len(records),
            "total_steps_seen": total,
            "wall_time": time.time(),
        }
        if extra:
            meta.update(extra)
        with open(path, "w") as f:
            f.write(json.dumps(meta) + "\n")
            for rec in records:
                f.write(json.dumps(rec, default=str) + "\n")
        return path

    @property
    def dumps(self) -> int:
        with self._lock:
            return self._dumps
