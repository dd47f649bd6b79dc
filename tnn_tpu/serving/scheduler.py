"""Continuous-batching scheduler: FCFS admission, per-step token budget,
preemption with recompute-requeue.

The scheduling model follows the Gemma-on-TPU serving comparison
(arXiv:2605.25645): running requests decode one token every engine step;
queued requests are admitted (prefilled) whenever the decode batch has a free
slot, the step's token budget allows the prompt, and the KV pool has blocks —
so the batch refills continuously instead of draining to empty like static
batching.

Preemption is recompute-style (vLLM's default): when the pool runs dry the
LATEST-admitted running request frees all its blocks and re-queues at the
FRONT of the wait queue, carrying its generated-so-far tokens as an extended
prompt. Under greedy decoding the re-prefill reproduces the same KV state
token-for-token, so preemption is invisible in the output stream.

The scheduler is pure host-side policy — it never touches device arrays. The
engine executes its plans and reports back via admit/finish/requeue.

Pool gating is mesh-agnostic by construction: every admission/chunk decision
consults ``pool.num_allocatable``, which under sequence parallelism (sp>1)
already reports ``sp * min(free blocks per shard)`` — the BOTTLENECK shard
gates admission, since a request's next block must come from the round-robin
owner of its table position. No scheduler code branches on sp.
"""
from __future__ import annotations

import enum
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import numpy as np


class RequestState(enum.Enum):
    QUEUED = "queued"        # waiting for admission (fresh or preempted)
    RUNNING = "running"      # holds pool blocks; decodes every step
    FINISHED = "finished"    # completed normally (length | stop_token)
    FAILED = "failed"        # isolated fault: alloc failure, NaN logits,
    #                          injected/step exception, preemption budget
    CANCELLED = "cancelled"  # client called engine.cancel(rid)
    TIMED_OUT = "timed_out"  # deadline_s / max_queue_s expired


#: States a request never leaves; every submitted request must reach one —
#: the chaos suite's core invariant.
TERMINAL_STATES = frozenset({RequestState.FINISHED, RequestState.FAILED,
                             RequestState.CANCELLED, RequestState.TIMED_OUT})


class AdmissionRejected(RuntimeError):
    """Structured backpressure from ``InferenceEngine.submit``: the wait
    queue is at ``max_queue_depth`` under the ``reject`` admission policy.
    Clients retry later (or the server runs ``admission_policy="block"``)."""

    def __init__(self, queue_depth: int, max_queue_depth: int):
        self.queue_depth = queue_depth
        self.max_queue_depth = max_queue_depth
        super().__init__(
            f"queue full: {queue_depth} waiting >= max_queue_depth "
            f"{max_queue_depth}")


@dataclass
class Request:
    """One generation request plus its engine-managed lifecycle state."""
    rid: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    stop_token: Optional[int] = None
    submit_time: float = 0.0
    deadline_s: Optional[float] = None   # total wall budget from submit
    max_queue_s: Optional[float] = None  # max continuous time spent QUEUED
    priority: int = 0                    # smaller = more important; only
    #                                      consulted when shedding under
    #                                      overload (admission stays FCFS)

    # -- engine-managed --
    state: RequestState = RequestState.QUEUED
    block_table: List[int] = field(default_factory=list)
    summary_table: List[int] = field(default_factory=list)  # a windowed
    #                                     pool's second kind of page: one row
    #                                     a finished chunk (kv_pool.py)
    window_table: List[int] = field(default_factory=list)  # a pool of two
    #                                     page groups: the window layers'
    #                                     pages it still holds, from logical
    window_base: int = 0                # page ``window_base`` (kv_pool.py)
    state_slot: int = 0                 # a pool with state slots: the slot
    #                                     it holds while it runs (0: none),
    #                                     and the position each of its two
    #                                     snapshots was taken at (kv_pool.py)
    snap_at: List[Optional[int]] = field(default_factory=lambda: [None, None])
    cache_len: int = 0                  # tokens resident in the KV pool
    prefill_len: int = 0                # total tokens the current (re-)
    #                                     prefill must push; while cache_len
    #                                     is short of it the row is mid-
    #                                     prefill and takes chunks, not
    #                                     decode tokens (set at admission)
    next_token: Optional[int] = None    # sampled but not yet fed back
    out_tokens: List[int] = field(default_factory=list)
    preemptions: int = 0
    migrations: int = 0                 # crash/failover re-admissions
    migration_budget: Optional[int] = None  # max migrations before the
    #                                     request is FAILED as poison — a
    #                                     request that keeps crashing its
    #                                     engine must not wedge the restart
    #                                     loop (None = engine default)
    ttft_s: Optional[float] = None
    finish_reason: str = ""
    error: str = ""                     # detail for FAILED/CANCELLED/TIMED_OUT
    queued_time: float = 0.0            # last transition into QUEUED
    trace_id: str = ""                  # request-scoped trace id; assigned at
    #                                     submit (router- or engine-derived)
    #                                     and carried across migrations so one
    #                                     id spans every replica the request
    #                                     touched
    # -- latency breakdown (wall seconds accumulated across requeues) --
    queued_s: float = 0.0               # total time spent QUEUED
    prefill_s: float = 0.0              # total (re-)prefill wall time
    decode_s: float = 0.0               # total decode-phase wall time
    stall_s: float = 0.0                # decode-phase steps that emitted no
    #                                     token for this row (subset of
    #                                     decode_s — crash retries, batch
    #                                     stalls behind peer prefills)
    host_gap_s: float = 0.0             # wall time this row spent waiting on
    #                                     HOST bookkeeping between a step's
    #                                     fetch and the next dispatch (the
    #                                     gap the overlapped loop closes)
    phase: str = ""                     # "" | "prefill" | "decode" (engine-
    phase_t0: float = 0.0               # managed clock for the accumulators)

    @property
    def is_terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def latency_breakdown(self) -> Dict[str, float]:
        """Per-request wall-time attribution for terminal events (ms):
        where this request's lifetime actually went."""
        return {
            "queued_ms": round(self.queued_s * 1e3, 3),
            "prefill_ms": round(self.prefill_s * 1e3, 3),
            "decode_ms": round(self.decode_s * 1e3, 3),
            "stalled_ms": round(self.stall_s * 1e3, 3),
            "host_gap_ms": round(self.host_gap_s * 1e3, 3),
            "preemptions": self.preemptions,
            "migrations": self.migrations,
        }

    @property
    def num_generated(self) -> int:
        return len(self.out_tokens)

    @property
    def resume_tokens(self) -> np.ndarray:
        """The sequence a (re-)prefill must push through the model: the
        prompt plus every generated token already fed back. The pending
        ``next_token`` (sampled, not yet fed) is excluded — after preemption
        it is carried over as-is, so recovery never re-samples."""
        if not self.out_tokens:
            return self.prompt
        fed = np.asarray(self.out_tokens[:-1], np.int32)
        return np.concatenate([self.prompt, fed])


@dataclass
class StepPlan:
    #: requests admitted this step (they join the running set at once and
    #: push their first chunk in the same step)
    prefills: List[Request]
    decodes: List[Request]
    #: rid -> live tokens to push this step for rows still mid-prefill
    chunks: Dict[int, int] = field(default_factory=dict)


class Scheduler:
    """FCFS continuous batching over a PagedKVPool.

    ``token_budget`` caps the model tokens processed per step (a decode row
    costs 1 and takes priority; prompt chunks fill the rest). A request is
    still admitted with no budget left when it is the only work — otherwise
    it could never start.

    ``chunk_size``: Sarathi-style chunked prefill. Prompts enter the running
    set immediately and push at most ``chunk_size`` prompt tokens per step,
    co-scheduled with the decode rows inside the same token budget, so a
    long prompt never stalls the decode stream for a whole prompt-length
    forward pass.

    ``spec_tokens`` > 0 (the engine sets it when speculative decoding is on)
    charges each decode-phase row ``1 + spec_tokens`` budget per step: a
    spec row scores its pending token PLUS up to ``spec_tokens`` drafted
    candidates in one forward, and the budget must reflect that worst case
    even when a drafter proposes fewer (admission is planned before drafts
    are computed).
    """

    def __init__(self, max_batch_size: int = 8, token_budget: int = 2048,
                 chunk_size: int = 64, spec_tokens: int = 0):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0")
        self.max_batch_size = int(max_batch_size)
        self.token_budget = int(token_budget)
        self.chunk_size = int(chunk_size)
        self.spec_tokens = int(spec_tokens)
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []  # admission order (oldest first)
        # engine-wired PrefixCache (or None): admission PROBES it — read
        # only, no refcount moves — to budget a request's first chunk and
        # block demand against its cached prefix; the engine performs the
        # actual fork/COW at admit time. Nothing runs between schedule()
        # and admit, so both see the same index and agree exactly.
        self.prefix_cache = None

    # -- queue state ----------------------------------------------------------

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def submit(self, req: Request) -> None:
        req.state = RequestState.QUEUED
        req.queued_time = time.perf_counter()
        self.waiting.append(req)

    # -- planning -------------------------------------------------------------

    def _headroom(self, pool, spare: int = 0) -> int:
        """Blocks an admission may plan against. A windowed pool admits a
        request only if it fits TO ITS LAST TOKEN beside what the running
        requests may still take (their exact window whole, a summary row for
        every chunk to come): exact pages come and go with the windows, so
        "fits now" says nothing, and a steady run never preempts. A pool
        of two page groups likewise: its window pages come and go.

        ``spare``: blocks the caller took for the running rows AHEAD of the
        step being planned (``would_admit``). A plain pool counts them back
        in; here a block a row holds early is a block it no longer owes, so
        the sum stands as it is."""
        if pool.window is None and not pool.sliding:
            return pool.num_allocatable + spare
        owed = sum(
            pool.lifetime_blocks(len(r.prompt) + r.max_new_tokens)
            - len(r.block_table) - len(r.summary_table)
            - len(r.window_table) for r in self.running)
        return pool.num_allocatable - owed

    def plan_running(self, pool, lens: Optional[Dict[int, int]] = None):
        """The running rows' part of a step: (chunk grants of the rows still
        mid-prefill, the token budget left for admissions). Each
        decode-phase row costs 1 budget token (plus ``spec_tokens`` drafted
        candidates scored alongside it); rows mid-prefill take up to
        chunk_size more of their prompt, and the oldest always advances at
        least one token.

        Reads only. ``lens`` (rid -> resident positions) plans the rows at
        PREDICTED lengths, a row it leaves out at its committed one: the
        step the engine dispatches ahead of its predecessors' commits
        (``InferenceEngine.try_speculate``) is the step ``schedule`` plans
        once they have landed, by this arithmetic and no copy of it."""
        lens = lens or {}
        chunks: Dict[int, int] = {}
        budget = self.token_budget
        prefilling: List[tuple] = []
        for req in self.running:
            cache_len = lens.get(req.rid, req.cache_len)
            if cache_len >= req.prefill_len:
                budget -= 1 + self.spec_tokens
            else:
                prefilling.append((req, cache_len))
        for i, (req, cache_len) in enumerate(prefilling):
            rem = req.prefill_len - cache_len
            avail = budget if budget >= 1 else (1 if i == 0 else 0)
            take = min(self.chunk_size, rem, avail,
                       pool.room_in_window(cache_len))
            if take <= 0:
                continue
            chunks[req.rid] = take
            budget -= take
        return chunks, budget

    def _has_free_row(self, admitted: int = 0) -> bool:
        """Somebody waits and a row is free beside the running requests and
        the ``admitted`` ones of the step being planned."""
        return bool(self.waiting) and \
            len(self.running) + admitted < self.max_batch_size

    def _admission(self, pool, admitted: int, budget: int, planned: int,
                   spare: int = 0):
        """The admission test for the head of the queue, given a free row:
        (first chunk, blocks the plan pays) when the step would admit it
        after ``admitted`` others that took ``planned`` blocks, None when
        the budget or the pool says no. Reads only: nothing leaves the
        queue, no request is written to, the prefix cache is probed.

        With a prefix cache, cached prompt positions cost no chunk budget
        (their KV is already resident) and matched blocks cost no new
        allocation — only the uncached tail is budgeted. Reviving an
        EVICTABLE matched block does consume reclaimable capacity, so it is
        counted against the headroom alongside fresh blocks."""
        req = self.waiting[0]
        sole = not self.running and not admitted
        if budget < 1 and not sole:
            return None
        slots = getattr(pool, "slots", None)
        if slots is not None and slots.num_free <= admitted:
            return None     # a state slot is counted beside the pages
        tokens = req.resume_tokens
        cached = forked = revive = 0
        if self.prefix_cache is not None:
            mb, cached, cow = self.prefix_cache.probe(tokens)
            if mb:
                # a full-cover hit forks all but the last matched block
                # (the engine gives that one a fresh COW copy, counted
                # in nb below via blocks_for - forked)
                shared = mb[:-1] if cow else mb
                forked = len(shared)
                revive = sum(1 for b in shared if pool.is_evictable(b))
        take = min(self.chunk_size, len(tokens) - cached,
                   max(budget, 1), pool.room_in_window(cached))
        nb = pool.admission_blocks(
            cached + take, len(req.prompt) + req.max_new_tokens) - forked
        if planned + nb + revive > self._headroom(pool, spare):
            return None
        return take, nb + revive

    def would_admit(self, pool, spare: int = 0,
                    lens: Optional[Dict[int, int]] = None) -> bool:
        """Whether ``schedule`` would admit the head of the queue into the
        step after the one the running rows are in, by ``schedule``'s own
        arithmetic and moving nothing: what the engine asks before it
        dispatches that step ahead of time, and again before it adopts it
        (``InferenceEngine.try_speculate``). An empty queue answers at once,
        a full batch without touching the pool. ``spare`` are the blocks the
        engine has already taken for the running rows' steps ahead, which
        ``schedule`` would still have found free (``_headroom``); ``lens``
        the running rows' predicted lengths (``plan_running``): a row still
        pushing its prompt takes its chunk out of the budget first."""
        if not self._has_free_row():
            return False
        _, budget = self.plan_running(pool, lens)
        return self._admission(pool, 0, budget, 0, spare) is not None

    def schedule(self, pool) -> StepPlan:
        """Plan one engine step: which queued requests to admit, and the
        running set to decode. Admission is strictly FCFS — a blocked
        queue head blocks everyone behind it (no out-of-order admission, so
        no starvation).

        Called only from the engine's build phase against COMMITTED state:
        under the overlapped loop every prior step's commit has already
        adopted its pool pages and scheduler transitions before the next
        ``schedule`` runs, so planning never sees a half-applied step. A
        step dispatched ahead of its predecessor's commit never comes
        here: it is legal only while ``would_admit`` says this method
        would admit nothing, waiting requests or none.

        Sarathi-style step packing (``plan_running``): each decode-phase
        running row costs 1 budget token; running rows still mid-prefill
        take up to chunk_size more of their prompt; what's left admits
        queued requests at chunk granularity (FCFS, ``_admission``), while
        a row is free. A sole request is always admitted even with
        budget < 1 (it could never start otherwise)."""
        chunks, budget = self.plan_running(pool)
        prefills: List[Request] = []
        planned_blocks = 0
        while self._has_free_row(len(prefills)):
            fit = self._admission(pool, len(prefills), budget,
                                  planned_blocks)
            if fit is None:
                break
            take, cost = fit
            req = self.waiting.popleft()
            req.prefill_len = len(req.resume_tokens)
            chunks[req.rid] = take
            budget -= take
            planned_blocks += cost
            prefills.append(req)
        return StepPlan(prefills=prefills, decodes=list(self.running),
                        chunks=chunks)

    # -- lifecycle callbacks (engine-driven) ----------------------------------

    def admit(self, req: Request) -> None:
        req.state = RequestState.RUNNING
        self.running.append(req)

    def finish(self, req: Request, reason: str = "length") -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        self.running.remove(req)

    def terminate(self, req: Request, state: RequestState,
                  error: str = "") -> None:
        """Move a request to a non-FINISHED terminal state (FAILED /
        CANCELLED / TIMED_OUT) from wherever it currently lives. The engine
        frees any pool blocks BEFORE calling this — the scheduler never
        touches device state."""
        if state not in TERMINAL_STATES or state is RequestState.FINISHED:
            raise ValueError(f"terminate() is for failure states, got {state}")
        if req in self.running:
            self.running.remove(req)
        else:
            try:
                self.waiting.remove(req)
            except ValueError:
                pass                     # already out of both structures
        req.state = state
        req.finish_reason = state.value
        req.error = error

    def shed_victim(self, priority: int) -> Optional[Request]:
        """Priority-aware load shedding at admission: when the queue is full,
        the queued request with the numerically LARGEST priority (least
        important) makes room for an arriving request of priority
        ``priority`` — but only when strictly less important than it, so
        equal-priority traffic keeps the plain reject behavior. Ties among
        candidates shed the newest (least sunk wait time). Running requests
        are never shed — their prefill work is paid for."""
        victim: Optional[Request] = None
        for req in self.waiting:
            if req.priority > priority and \
                    (victim is None or req.priority >= victim.priority):
                victim = req
        return victim

    def preempt_victim(self) -> Optional[Request]:
        """LIFO victim choice: the latest-admitted running request loses its
        blocks first (it has the least sunk prefill work)."""
        return self.running[-1] if self.running else None

    def requeue(self, req: Request) -> None:
        """Recompute-preemption: back to the FRONT of the queue so FCFS order
        is preserved; generated tokens ride along via ``resume_tokens``."""
        self.running.remove(req)
        req.state = RequestState.QUEUED
        req.queued_time = time.perf_counter()
        req.preemptions += 1
        self.waiting.appendleft(req)

    def migrate(self, req: Request) -> None:
        """Crash-migration re-admission: identical motion to ``requeue`` but
        charged to the per-request migration budget, not ``preemptions`` —
        the request lost its KV to an engine restart (or replica failover),
        not to pool pressure. The engine frees the blocks first, exactly as
        for preemption; ``resume_tokens`` + the pending ``next_token`` make
        the resumed stream token-exact under greedy decoding."""
        self.running.remove(req)
        req.state = RequestState.QUEUED
        req.queued_time = time.perf_counter()
        req.migrations += 1
        self.waiting.appendleft(req)
