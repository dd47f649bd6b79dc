"""InferenceEngine: request lifecycle over continuous batching + paged KV.

Ties the subsystem together:

    submit() --> Scheduler (FCFS queue) --> step():
        ONE mixed step packs decode rows (1 token each) and prefill CHUNKS
        (up to chunk_size prompt tokens each) into a single compiled program
      --> streamed tokens / finished requests

Chunked prefill: prompts are pushed ``chunk_size`` tokens at a time,
co-scheduled with the decode rows inside the same ``token_budget``, so a long
prompt arriving mid-stream never stalls decoding requests for a whole
prompt-length forward pass — the dominant TTFT/latency tail under mixed load.
Partially-prefilled requests persist their progress in pool blocks and take
their next chunk on later steps without recompute. Steps with no chunk work
run the pure-decode program.

Static-shape discipline (the whole point on XLA backends): the mixed step is
compiled per (max_batch_size, chunk-width bucket, assembly_width) with chunk
widths bucketed to powers of two — requests joining or leaving the batch
never retrace; absent rows are padded onto the pool's scratch block and
masked by the per-row q_lens/offsets (padding tokens write their KV to the
scratch page and output garbage that is never read), so N distinct prompt
lengths cost O(log N) compiles.

One serving path: every step program (``tnn_serve_decode``,
``tnn_serve_mixed_w<w>``, ``tnn_serve_spec_w<w>``) runs the model's
``apply_paged`` over the ragged paged-attention
kernel (ops/pallas/paged_attention.py), which consumes the pool's pages and
block tables directly: no cache is ever assembled. A model without that
method is refused at start-up. The pool's device arrays travel as ONE value
(``PagedKVPool.cache``), DONATED through every
jitted step, so XLA updates pages in place instead of copying the pool each
token.

Automatic prefix caching (default on; docs/serving.md
has the full design): admission probes a content-addressed block index
(serving/prefix_cache.py) with the request's prompt; matched blocks are
``fork``ed into its block table — their tokens are already-resident KV and
cost ZERO prefill compute — and only the uncached tail is chunk-prefilled.
Full blocks completed by any prefill chunk are published back to the index.
Released blocks whose content is still indexed park in the pool's evictable
LRU instead of the free list and are reclaimed on demand, so the cache never
reduces effective capacity. A fully-cached prompt keeps its last token out of
the match (the recomputed tail produces the first-token logits) and takes a
copy-on-write clone of the block that token writes into — indexed blocks are
immutable. With the cache off behaviour and output streams are unchanged;
with it on, outputs stay token-exact because
matched KV is bit-identical to what the skipped prefill would have written.

Speculative decoding (``spec="ngram"`` / ``"draft"`` / a custom
``spec_decode.Drafter``; docs/serving.md has the full design): each decode
row packs its pending token plus up to ``spec_k`` drafted candidates as a
ragged ``q_lens = k+1`` row into the SAME mixed step — the multi-token
scoring primitive chunked prefill already compiled — and one forward
verifies all of them. Greedy rows accept the longest draft prefix matching
the per-position argmax; stochastic rows run standard rejection sampling
against the filtered target distribution. Accepted tokens commit through the
mixed step's page write; rejected tails roll back by truncating the row's
block table to its verified length (``pool.truncate``). Greedy output
streams are token-exact vs spec-off by construction — every committed token
is one the sequential decode would have produced — and unverified draft KV
is never published to the prefix cache (decode rows never publish at all).

Fault tolerance (docs/serving.md has the full failure-mode matrix): every
submitted request reaches a terminal state — FINISHED, FAILED, CANCELLED,
or TIMED_OUT — and failures are isolated per request. A pool-alloc failure,
non-finite logits (caught per row by the configurable logit guard), or an
oversized resume fails only the poisoned request, frees its blocks, and the
rest of the batch keeps decoding. Recompute-preemption is capped per request
(``preemption_budget``): a thrashing victim fails cleanly instead of
livelocking the pool. ``submit`` applies bounded admission
(``max_queue_depth`` with ``reject``/``block`` policy), ``cancel(rid)``
aborts a queued or running request, and per-request ``deadline_s`` /
``max_queue_s`` are enforced at the top of every step. An unattributable
decode-step exception is retried once when transient (injected faults fire
before the jitted call, so donated buffers are intact), else the live batch
aborts — queued requests keep the engine serving. A seeded
``faults.FaultPlan`` injects all of the above deterministically for chaos
tests.
"""
from __future__ import annotations

import itertools
import os
import time
from collections import Counter
from contextlib import nullcontext
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..models import sampling
from ..profiling.profiler import EventType, Profiler
from ..nn import moe as moe_lib
from ..nn.attention import snapshot_slots
from . import kv_pool as kv_pool_lib
from . import spec_decode
from . import step_build
from .faults import FaultInjected, FaultPlan
from .kv_pool import PagedKVPool, PoolExhausted
from .kv_tier import HostKVTier, tier_digest
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache
from .scheduler import (TERMINAL_STATES, AdmissionRejected, Request,
                        RequestState, Scheduler)
from .tracing import Tracer


# How far ``try_speculate`` dispatches ahead of the step in flight: enough
# steps to keep the device busy for SPECULATE_AHEAD_S while the host stands
# still (a shared machine stops every process for 80-140 ms now and then:
# PERF.md section 2; the device goes on with what is queued), at most
# SPECULATE_MAX, and one step more for every SPECULATE_RAMP steps
# adopted in a row, so that only a batch whose rows have stayed for a while
# queues deep: what an arrival into a free row finds queued it waits out.
SPECULATE_AHEAD_S = 0.2
SPECULATE_MAX = 12
SPECULATE_RAMP = 8


@jax.jit
def _splice_draft_row(toks, draft, row):
    """Write a device-resident draft into row ``row`` of the step's token
    matrix at column 1 (after next_token), entirely on-device. Jitted so the
    column constant never becomes an eager host->device transfer under
    TNN_DEBUG_SYNC=1."""
    return jax.lax.dynamic_update_slice(toks, draft, (row, jnp.int32(1)))


@jax.jit
def _splice_prev_tokens(toks, prev, idx, from_prev):
    """Finish a step's token array on the device from ``prev``, the
    unfetched samples of the step dispatched before it: row i's pending
    token (a decode step's ``toks[i]``, a mixed step's ``toks[i, 0]``)
    becomes ``prev[idx[i]]`` where ``from_prev[i]``, and stays what the host
    packed elsewhere (a chunk row's prompt, a resumed row's kept token,
    padding). The gather is what lets a row that finished its prompt move
    from the chunk rows to its place among the decode rows."""
    col = toks if toks.ndim == 1 else toks[:, 0]
    col = jnp.where(from_prev, prev[idx].astype(toks.dtype), col)
    return col if toks.ndim == 1 else toks.at[:, 0].set(col)


class _RowAhead(NamedTuple):
    """One running row as the commit of a dispatched step will leave it
    (``InferenceEngine._rows_after``): what the overlapped loop predicts
    the step after from."""
    req: Request
    cache_len: int
    generated: int
    # where its pending token is: a row of that step's unfetched samples,
    # ``_ON_HOST`` (``req.next_token``), None while it pushes its prompt
    src: Optional[int]

    @property
    def decoding(self) -> bool:
        return self.src is not None


_ON_HOST = -1


class _LaunchFailed(Exception):
    """The dispatch of a packed step raised (``InferenceEngine._launch``):
    reads as its cause, and carries the step's PRNG key for the caller that
    gives it back."""

    def __init__(self, cause: Exception, key):
        super().__init__(str(cause))
        self.key = key


def _stacked(counts) -> tuple:
    """A step program's expert counters as more outputs, ``(layers, held)``
    and, of a model with zero-compute experts, ``(layers, 2)``
    (``nn.moe.collect_counts``), or none: a model with no expert layer
    returns what it did."""
    return tuple(jnp.stack(c) for c in (counts, counts.zero) if c)


def _sampled(logits, poison, key, t, k, p):
    """The tail of a plain step program: a row's logits (B, V) with the
    chaos plan's ``poison`` added -> (its next token, whether they were
    finite)."""
    logits = logits + poison[:, None]
    ok = jnp.isfinite(logits).all(axis=-1)
    return sampling.sample_ragged(logits, key, t, k, p), ok


def refuse_windowed(model, *, prefix_cache=False, spec=False, tp=1, sp=1,
                    host_tier_bytes=0, kv_dtype="f32") -> Optional[str]:
    """One sentence saying why ``model`` does not serve with the first of
    these options that is on, or None. A model whose state is not "K and V
    blocks of heads for every position" runs on the normal path over pages
    of its own kind: EVA (an exact window beside chunk summaries: two kinds
    of page), latent attention (one row a token that is key and value at
    once, no head axis: latent pages) and sliding-window layers beside
    global layers (two groups of page, the window group's given back from
    behind the window). What assumes K/V blocks of heads, one table a
    request, is refused at start-up, by the engine and by ``tnn-serve``
    before it makes any weights. The fifth case: layers that keep a state
    updated in place (Gated DeltaNet) beside layers with pages: state slots
    beside the pool's pages."""
    window = getattr(model, "window", None)
    latent = getattr(model, "latent", None)
    groups = getattr(model, "page_groups", None)
    slots = getattr(model, "state_group", None)
    if not window and not latent and not groups and not slots:
        return None
    if slots:
        state = (f"a state updated in place at every position in "
                 f"{slots['layers']} of its layers (state slots beside the "
                 "pages), not K/V blocks of every position in every layer")
        whys = ("a cached block holds no state, and the state behind a "
                "shared prefix would have to be a snapshot of it",
                "a rejected draft has advanced the state, and the verify "
                "step keeps no snapshot to take it back to",
                "the state's heads and its kernel are not head-sharded",
                "a row's state is not block-sharded",
                "the state is float32 and the full layers' int8 pages have "
                "not been held against the reference beside it")
    elif groups:
        state = (f"sliding-window layers (a window of {groups['window']}) "
                 "beside global layers over two groups of page, not one "
                 "table of K/V blocks for every layer")
        whys = ("a cached block is one layer's page of one group, and the "
                "window layers give theirs back from behind the window",
                "a rejected draft may have given window pages back, and a "
                "release does not roll back",
                "the split of a packed table by layer kind is not "
                "head-sharded",
                "a window table's base and its walk are not block-sharded",
                "the int8 kernel has not been held against the reference "
                "under a window")
    elif window:
        state = (f"an exact window of {window} positions beside chunk "
                 "summaries, not K/V blocks of every position")
        whys = ("a cached block would have to carry the summaries of "
                "everything before it",
                "a rejected draft may have ended a window or written a "
                "summary, and neither rolls back",
                "the summary write and the two-segment kernel are not "
                "head-sharded",
                "exact and summary pages are not block-sharded",
                "summaries are written in the compute dtype")
    else:
        state = ("one latent row a token that is key and value at once, "
                 "not K/V blocks of heads")
        whys = ("the cache's copy-on-write and export move K and V pages",
                "the verify step returns no expert counters and is not "
                "held against the reference over latent pages",
                "a latent row has no head axis to shard",
                "latent pages are not block-sharded, and the softmax merge "
                "takes K/V heads",
                "a latent row's two parts would need two scales")
    for on, what, why in (
            (prefix_cache, "prefix sharing (the prefix cache; "
             "--no-prefix-cache)", whys[0]),
            (spec, "speculative decoding (spec)", whys[1]),
            (tp > 1, "tensor parallelism (tp)", whys[2]),
            (sp > 1, "sequence parallelism (sp)", whys[3]),
            (bool(host_tier_bytes), "the host KV tier",
             "it demotes prefix-cache blocks"),
            (kv_dtype == "int8", "int8 pages (kv_dtype)", whys[4])):
        if on:
            return (f"{type(model).__name__} keeps {state}: it does not "
                    f"serve with {what}: {why}")
    return None


class StepInFlight:
    """Handle for one dispatched-but-uncommitted engine step.

    ``begin_step`` fills it with the step's flight-recorder note and one
    record per launched program (device references only — nothing is
    fetched at build time); ``finish_step`` fetches the single result
    bundle and runs the commit phase against it. ``ahead`` holds the
    speculatively dispatched successor steps, oldest first (see
    ``InferenceEngine.try_speculate``)."""

    __slots__ = ("step_seq", "note", "fired_before", "t0", "gen_before",
                 "events", "recs", "done", "ahead", "latency_s")

    def __init__(self, step_seq: int, note: Dict[str, Any],
                 fired_before: Optional[Counter], t0: float):
        self.step_seq = step_seq
        self.note = note
        self.fired_before = fired_before
        self.t0 = t0
        self.gen_before: Dict[int, int] = {}
        self.events: Dict[str, List] = {"tokens": [], "finished": [],
                                        "failed": [], "timed_out": []}
        self.recs: List[Dict[str, Any]] = []
        self.done = False
        self.ahead: List[Dict[str, Any]] = []
        self.latency_s = 0.0


class InferenceEngine:
    """Continuous-batching inference over one GPT2-family model.

    Parameters
    ----------
    model, params : the module tree and its params (``variables["params"]``).
    num_blocks, block_size : KV pool geometry (block 0 is reserved scratch).
    max_batch_size : decode batch width the step is compiled at.
    token_budget : per-step cap on model tokens (decodes + prompt chunks).
    chunk_size : prompt tokens a request may push per mixed step (chunk
        widths are bucketed to powers of two for compile-cache boundedness).
    prefix_cache : automatic prefix caching. False disables matching,
        publishing, and the evictable pool entirely.
    prefix_cache_min_hit_blocks : ignore cache matches shorter than this
        many full blocks (a tiny hit still costs a fork + index churn).
    max_seq_len : per-request position cap (prompt + generated); defaults to
        the smaller of model.max_len and the pool's whole capacity.
    max_queue_depth : bounded admission — waiting requests beyond this make
        ``submit`` apply backpressure (0 = unbounded).
    admission_policy : "reject" (submit raises ``AdmissionRejected``) or
        "block" (submit drives ``step()`` until the queue drains below the
        bound — single-threaded backpressure).
    preemption_budget : max recompute-preemptions per request before the
        victim FAILs instead of requeueing (None = unlimited; caps the
        two-large-requests livelock).
    logit_guard : per-row non-finite logit detection; a poisoned row FAILs
        its request while the rest of the batch keeps its tokens.
    spec : speculative decoding — "off", "ngram" (self-speculative n-gram
        lookup over each row's own context), "draft" (a small stand-in model
        proposes; needs ``draft_model``/``draft_params``), or any
        ``spec_decode.Drafter`` instance (the mixed step is the
        verification primitive).
    spec_k : max drafted tokens per decode row per step (the verified step
        scores ``k+1`` positions).
    draft_model, draft_params : the stand-in model for ``spec="draft"``;
        must share the target model's vocabulary.
    faults : optional ``faults.FaultPlan`` for deterministic chaos testing.
    prefix_publish_max_occupancy : degradation mode — suspend prefix-cache
        publishes while live-request pool occupancy exceeds this fraction
        (growing the evictable set under pressure just churns reclaims;
        matching stays on). Counted in ``stats()["publish_suspended"]``.
    profiler : optional profiling.Profiler, the second sink of the engine's
        spans (the first, the JAX profiler's trace, is always on: see
        ``tracing.Tracer`` and docs/observability.md).
    trace : request-scoped tracing — every request gets a ``trace_id`` and
        the engine's phase spans (``serve.build`` / ``dispatch`` / ``fetch``
        / ``commit`` / ``deferred``) and admission/chunk/preemption/publish/
        finish instants also land in the ``Profiler`` timeline, one Perfetto
        track per profiler ``source``. Auto-creates a
        ``Profiler(source="engine")`` when none is given. Tracing is
        host-side only: traced runs are token-exact vs untraced and the
        TNN_DEBUG_SYNC transfer guard stays clean.
    overlap : double-buffered engine loop. ``begin_step`` builds and
        DISPATCHES a step without fetching its results; ``finish_step``
        later fetches the step's one sampled-token/ok/accepts bundle and
        commits it, and host bookkeeping nothing downstream depends on
        (prefix publishes + their instants) lands on a deferred queue
        (``run_deferred``) drained while the next step runs on-device.
        The drive loops (``run_until_complete``, the supervisor tick) pair
        begin/finish around the deferred work and may speculatively
        dispatch steps N+1, N+2, ... from predicted row states before step
        N commits (``try_speculate``, as deep as ``_speculate_depth`` says:
        decode steps and the mixed steps that carry a prompt's next chunks,
        at which the scheduler would admit nothing, with a queue behind a
        full batch as well as with none; mispredictions roll back and
        rebuild).
        Token-exact vs overlap-off — a direct
        ``step()`` call stays fully synchronous either way. Default off;
        ``tnn-serve`` turns it on (``--no-overlap`` opts out).
    device : the one device this engine's params, pool and step inputs live
        on (None = JAX's default device). How a fleet puts replica i on
        chip i; a ``tp``/``sp`` engine spans a mesh instead and refuses it.
    """

    def __init__(self, model, params, *, num_blocks: int = 64,
                 block_size: int = 16, max_batch_size: int = 8,
                 token_budget: int = 2048, chunk_size: int = 64,
                 prefix_cache: bool = True,
                 prefix_cache_min_hit_blocks: int = 1,
                 max_seq_len: Optional[int] = None,
                 max_queue_depth: int = 0,
                 admission_policy: str = "reject",
                 preemption_budget: Optional[int] = 16,
                 migration_budget: Optional[int] = 3,
                 logit_guard: bool = True, faults: Optional[FaultPlan] = None,
                 prefix_publish_max_occupancy: float = 0.95,
                 spec: Any = "off", spec_k: int = 4,
                 draft_model=None, draft_params=None,
                 profiler: Optional[Profiler] = None, trace: bool = False,
                 overlap: bool = False, kv_dtype: str = "f32",
                 quant_weights: bool = False, tp: int = 1, sp: int = 1,
                 host_tier_bytes: int = 0, seed: int = 0, device=None):
        if getattr(model, "kv_cache_dtype", None):
            raise ValueError(
                "the paged pool stores compute-dtype pages; "
                f"kv_cache_dtype={model.kv_cache_dtype!r} models are not "
                "servable — quantize the POOL instead (kv_dtype='int8')")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(f"kv_dtype must be 'f32' or 'int8', "
                             f"got {kv_dtype!r}")
        self._probe_paged(model)
        if admission_policy not in ("reject", "block"):
            raise ValueError(
                f"unknown admission_policy {admission_policy!r}")
        if max_queue_depth < 0:
            raise ValueError("max_queue_depth must be >= 0 (0 = unbounded)")
        if preemption_budget is not None and preemption_budget < 0:
            raise ValueError("preemption_budget must be >= 0 or None")
        if migration_budget is not None and migration_budget < 0:
            raise ValueError("migration_budget must be >= 0 or None")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if prefix_cache_min_hit_blocks < 1:
            raise ValueError("prefix_cache_min_hit_blocks must be >= 1")
        if host_tier_bytes < 0:
            raise ValueError("host_tier_bytes must be >= 0 (0 = no tier)")
        if host_tier_bytes and not prefix_cache:
            raise ValueError(
                "host_tier_bytes requires the prefix cache (tier entries "
                "are addressed by its chain keys) — enable prefix_cache, or "
                "set host_tier_bytes=0")
        if host_tier_bytes and tp > 1:
            raise ValueError(
                "host_tier_bytes with tp>1 is unsupported — demoted page "
                "slices would need a cross-shard gather/scatter; run the "
                "host tier on single-chip replicas")
        if host_tier_bytes and sp > 1:
            raise ValueError(
                "host_tier_bytes with sp>1 is unsupported — a demoted "
                "block's pages live on one context-mesh shard and the "
                "re-admission write would need per-shard routing; run the "
                "host tier on single-chip replicas")
        if device is not None and (tp > 1 or sp > 1):
            raise ValueError(
                "device= places a single-chip engine; a tp/sp engine spans "
                "its own mesh")
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.drafter: Optional[spec_decode.Drafter] = None
        self.spec_mode = spec if isinstance(spec, str) else \
            getattr(spec, "name", "custom")
        self.spec_k = int(spec_k)
        if isinstance(spec, spec_decode.Drafter):
            self.drafter = spec
        elif spec == "ngram":
            self.drafter = spec_decode.NGramDrafter()
        elif spec == "draft":
            if draft_model is None or draft_params is None:
                raise ValueError("spec='draft' needs draft_model and "
                                 "draft_params")
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft model vocab {draft_model.vocab_size} != target "
                    f"vocab {model.vocab_size} — drafted token ids must be "
                    "meaningful to the target")
            self.drafter = spec_decode.DraftModelDrafter(draft_model,
                                                         draft_params)
        elif spec != "off":
            raise ValueError(f"unknown spec {spec!r} (off | ngram | draft | "
                             "a spec_decode.Drafter)")
        if self.drafter is not None and self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        self.max_queue_depth = int(max_queue_depth)
        self.admission_policy = admission_policy
        self.preemption_budget = preemption_budget
        self.migration_budget = migration_budget
        self.logit_guard = bool(logit_guard)
        self.faults = faults
        self.model = model
        self.kv_dtype = kv_dtype
        self.quant_weights = bool(quant_weights)
        # a model whose state is not "K and V of every position" (EVA: an
        # exact window beside chunk summaries) runs on the same path, with a
        # pool of two kinds of page; what assumes K/V blocks is refused
        window = getattr(model, "window", None)
        refusal = refuse_windowed(
            model, prefix_cache=prefix_cache,
            spec=self.drafter is not None, tp=tp, sp=sp,
            host_tier_bytes=host_tier_bytes, kv_dtype=kv_dtype)
        if refusal:
            raise ValueError(refusal)
        # tensor parallelism: tp > 1 shards attention heads and the paged
        # pool's head axis over a mesh of tp devices; all host-side
        # bookkeeping stays replicated (serving/tp.py). _tp is None at
        # tp=1 and every TP branch below keys off it, so the single-chip
        # configuration traces byte-identical programs to before.
        self.tp = int(tp)
        self._tp = None
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if self.tp > 1:
            if self.quant_weights:
                raise ValueError(
                    "quant_weights with tp>1 is unsupported — Int8Weight "
                    "leaves don't column-shard; serve fp weights under TP")
            if getattr(model, "moe_experts", 0):
                raise ValueError(
                    "tensor-parallel serving does not support MoE models "
                    "(expert dispatch is not head-sharded)")
            from . import tp as tp_lib
            self._tp = tp_lib.TPContext(model, params, self.tp)
            params = self._tp.params
        # sequence parallelism: sp > 1 range-partitions the paged pool's
        # BLOCK axis over a context mesh of sp devices, so the aggregate
        # pool (and thus max servable context) is sp x one chip's. Params
        # stay fully replicated; block tables are staged per-shard
        # (serving/sp.py) and each shard's attention sweep merges via one
        # online-softmax psum per layer. _sp is None at sp=1 and every SP
        # branch below keys off it, so the single-chip configuration
        # traces byte-identical programs to before.
        self.sp = int(sp)
        self._sp = None
        if self.sp < 1:
            raise ValueError(f"sp must be >= 1, got {sp}")
        if self.sp > 1:
            if self.tp > 1:
                raise ValueError(
                    "sp>1 with tp>1 is unsupported this engine — the "
                    "context mesh and the head mesh would need a 2-D "
                    "shard_map; pick ONE of sp / tp per replica")
            if self.quant_weights:
                raise ValueError(
                    "quant_weights with sp>1 is unsupported — "
                    "quantize_for_decode re-materializes leaves off the "
                    "context mesh; serve fp weights under SP")
            if getattr(model, "moe_experts", 0):
                raise ValueError(
                    "sequence-parallel serving does not support MoE models "
                    "(expert dispatch is not sequence-sharded)")
            from . import sp as sp_lib
            self._sp = sp_lib.SPContext(model, params, self.sp)
            params = self._sp.params
        # the model the compiled step bodies trace: the head-sharded
        # adapter under TP (same interface, per-shard math), the
        # block-sharded adapter under SP, the model itself otherwise.
        # Host-side math keeps reading self.model. ``_mesh`` is whichever
        # context spans this engine's mesh (None at tp=sp=1)
        self._mesh = self._tp or self._sp
        self._step_model = self._mesh.model if self._mesh else model
        # compile-key suffix: int8 pools trace different step programs
        # (QuantPages operands), so their cache entries must never collide
        # with f32 ones; likewise tp>1 / sp>1 (shard_map bodies). The
        # f32/tp=1/sp=1 configuration appends () — keys stay byte-identical
        self._kv_key = (("int8",) if kv_dtype == "int8" else ()) + \
            ((f"tp{self.tp}",) if self.tp > 1 else ()) + \
            ((f"sp{self.sp}",) if self.sp > 1 else ())
        if self.quant_weights:
            from ..nn import quant as _quant
            params = _quant.quantize_for_decode(params)
        self.params = params
        # a latent model's "head" is its one cached row (whole lanes)
        latent_row = getattr(model, "latent_row", None)
        self.head_dim = latent_row or getattr(model, "head_dim", None) \
            or model.d_model // model.num_heads
        # window layers beside global layers: ONE layer of pages, each a
        # layer's of one of two groups (kv_pool: Two page groups)
        groups = getattr(model, "page_groups", None)
        if self._mesh is not None:
            page_sharding = self._mesh.page_sharding
        elif device is not None:
            page_sharding = jax.sharding.SingleDeviceSharding(device)
        else:
            page_sharding = None
        self.pool = PagedKVPool(
            num_layers=1 if groups else model.cache_layers,
            num_kv_heads=model.num_kv_heads,
            head_dim=self.head_dim, num_blocks=num_blocks,
            block_size=block_size, dtype=model.policy.compute_dtype,
            kv_dtype=kv_dtype, sharding=page_sharding, sp=self.sp,
            window=window, chunk=getattr(model, "chunk", None),
            latent=bool(latent_row), groups=groups,
            state=getattr(model, "state_group", None),
            state_rows=max_batch_size)
        self.pool.fault_plan = faults
        # static gauge extras spliced into every _health_gauges refresh:
        # lets operators spot a misconfigured replica from /healthz alone
        self._gauge_extras: Dict[str, Any] = {
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_token": self.pool.kv_bytes_per_token,
            "quant_weights": int(self.quant_weights),
            "tp_degree": self.tp,
            # the TP headline: each chip holds 1/tp of every page's heads
            "kv_bytes_per_token_per_shard":
                (self.pool.kv_bytes_per_token +
                 self.pool.kv_scale_bytes_per_token) // self.tp,
            "sp_degree": self.sp,
            # the SP headline: each chip holds 1/sp of the pool's BLOCKS
            # (whole tokens — per-token bytes are unchanged; the pool is
            # sp x deeper in aggregate)
            "pool_blocks_per_shard": self.pool.blocks_per_shard,
            "host_tier_max_bytes": int(host_tier_bytes),
        }
        cap = min(model.max_len, self.pool.token_capacity)
        self.max_seq_len = min(max_seq_len or cap, cap)
        # fixed table width: every step's block table has this many entries
        # per row (padded with scratch), so ONE compile covers all batch states
        self.blocks_per_seq = self.pool.table_width(self.max_seq_len)
        if self.sp > 1 and self.blocks_per_seq % self.sp:
            raise ValueError(
                f"assembly width blocks_per_seq={self.blocks_per_seq} does "
                f"not divide over sp={self.sp} shards — the round-robin "
                f"placement would leave shards sweeping unequal table "
                f"spans; pick max_seq_len (or num_blocks/block_size) so "
                f"ceil(max_seq_len / block_size) is a multiple of sp")
        self.assembly_len = self.blocks_per_seq * block_size
        self.chunk_size = int(chunk_size)
        self.scheduler = Scheduler(
            max_batch_size=max_batch_size, token_budget=token_budget,
            chunk_size=self.chunk_size,
            spec_tokens=self.spec_k if self.drafter is not None else 0)
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            self.prefix_cache = PrefixCache(
                block_size=block_size,
                min_hit_blocks=prefix_cache_min_hit_blocks)
            # pool.free parks still-indexed blocks in the evictable LRU;
            # pool.alloc reports reclaimed ones so the index forgets them
            self.pool.evictable_filter = self.prefix_cache.contains_block
            self.pool.reclaim_hook = self.prefix_cache.drop_blocks
        # host-RAM KV tier (elastic memory): reclaimed-but-indexed blocks
        # demote to a bounded host buffer instead of vanishing, and admit
        # back on a prefix hit through a digest-verified device_put + the
        # existing evictable-revive path. demote_hook fires BEFORE
        # reclaim_hook, while the cache still maps block -> chain key.
        self.kv_tier: Optional[HostKVTier] = None
        if host_tier_bytes:
            self.kv_tier = HostKVTier(int(host_tier_bytes),
                                      fault_plan=faults)
            self.pool.demote_hook = self._demote_blocks
        # the scheduler PROBES the cache (read-only) to budget admissions
        self.scheduler.prefix_cache = self.prefix_cache
        self.prefix_publish_max_occupancy = float(prefix_publish_max_occupancy)
        self._last_decode_emit: Optional[float] = None
        if trace and profiler is None:
            profiler = Profiler(source="engine")
        self.profiler = profiler
        self.metrics = ServingMetrics(profiler)
        self.tracer = Tracer(profiler if trace else None)
        if self._mesh is not None:
            # every TP step dispatch records a serve.allreduce span (the
            # 2-psum/layer collective cost is the TP tax worth watching),
            # every SP one a serve.spmerge span (one online-softmax merge
            # psum per layer is the SP tax)
            self._mesh.tracer = self.tracer
        self.step_seq = 0                   # monotonically counts step() calls
        self._step_note: Optional[Dict[str, Any]] = None
        self._finished_note: Optional[Dict[str, Any]] = None
        self.overlap = bool(overlap)
        self._flight: Optional[StepInFlight] = None
        self._deferred: List[Callable[[], None]] = []
        # PRNG keys of abandoned speculative dispatches, oldest first; the
        # rebuilds reuse them so the key-consumption sequence matches
        # overlap-off
        self._reuse_keys: List[Any] = []
        self._adopted_run = 0       # speculative steps adopted in a row
        self._t_fetch_done: Optional[float] = None
        # the open phase of the worker's time (``_open_phase``): (span,
        # start, observer) of a serve.build or serve.commit in progress
        self._phase: Optional[tuple] = None
        # seconds of ``serve.put`` a step spent before its launch's own
        self._put_pending = 0.0
        # last step's wall time, exposed through the health gauges so the
        # router's health scoring can see a gray-slow replica without ever
        # reaching into the engine
        self._last_step_latency_s = 0.0
        self._health_gauges: Dict[str, Any] = {
            "queue_depth": 0, "num_running": 0, "step_latency_s": 0.0,
            "tier_blocks": 0, **self._gauge_extras}
        self.requests: Dict[int, Request] = {}
        self._rid = itertools.count()
        # on the engine's device, so every split (and the step it feeds)
        # stays there instead of hopping over from the default device
        self._key = jax.device_put(jax.random.PRNGKey(seed), device)
        self._jit: Dict[Any, Any] = {}
        self._attn_groups: Dict[int, Any] = {}   # step width -> _attn_group
        # compiled program -> the name ``_jit_step`` gave it, and what a
        # serve.dispatch says of it
        self._program_names: Dict[Any, str] = {}
        self._dispatch_attrs: Dict[Any, Dict[str, Any]] = {}
        # TNN_DEBUG_SYNC=1: run every step under jax.transfer_guard
        # ("disallow") — the dynamic complement to tnnlint's static
        # host-sync-in-step-path rule. All intentional step inputs go
        # through _put (explicit device_put) and all fetches through
        # jax.device_get, so any implicit transfer left on the step path
        # raises instead of silently stalling the pipeline.
        self.debug_sync = os.environ.get("TNN_DEBUG_SYNC", "") == "1"
        # always None: there is one serving path and nothing to fall back
        # from. Kept because the benchmark reads it
        # (chipbench/drivers/serve_stdin.py:277-278).
        self.paged_fallback_reason: Optional[str] = None
        if self.pool.slots is not None:
            # the roll-back's one program, warmed like the step programs
            # are: nothing compiles when a chain is first rolled back
            none = self._put(np.zeros((max_batch_size,), np.int32))
            self.pool.state = self._restore_fn()(self.pool.state, none, none)

    @staticmethod
    def _probe_paged(model) -> None:
        """The start-up check of the one serving path: the step programs
        call the model's ``apply_paged`` and nothing else."""
        if not hasattr(model, "apply_paged"):
            raise ValueError(
                f"{type(model).__name__} has no apply_paged: serving "
                "decodes straight against pool pages (see "
                "nn/transformer.PagedDecoder)")

    # -- request lifecycle ----------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
               stop_token: Optional[int] = None,
               deadline_s: Optional[float] = None,
               max_queue_s: Optional[float] = None,
               priority: int = 0,
               migration_budget: Optional[int] = None,
               trace_id: Optional[str] = None) -> int:
        """Queue a generation request; returns its request id.

        ``deadline_s`` bounds the request's total wall time from submit;
        ``max_queue_s`` bounds one continuous stretch in the wait queue —
        either expiring transitions it to TIMED_OUT at the next step.

        With ``max_queue_depth`` set, a full queue makes submit apply
        backpressure: policy "reject" raises ``AdmissionRejected``; policy
        "block" drives ``step()`` until a slot opens.

        ``priority`` (smaller = more important) only matters under that
        backpressure: before rejecting, submit sheds the least-important
        queued request (strictly larger priority value) to make room — so
        overload degrades background traffic first instead of uniformly.
        Equal-priority traffic keeps the plain reject/block behavior.

        ``migration_budget`` caps how many crash/failover re-admissions
        (``migrate_running``) this request may take before it is FAILED as
        poison; None inherits the engine default.

        ``trace_id`` names the request's trace (a router passes its global
        id so one trace spans every replica the request touched); None
        derives a deterministic ``t<rid>``.
        """
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt.size + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} "
                f"exceeds max_seq_len {self.max_seq_len}")
        if self.pool.lifetime_blocks(total) > self.pool.capacity:
            raise ValueError(
                f"request needs {self.pool.lifetime_blocks(total)} blocks but "
                f"the pool only has {self.pool.capacity} — it could never run")
        if self.max_queue_depth and \
                self.scheduler.queue_depth >= self.max_queue_depth:
            if self.admission_policy == "reject":
                victim = self.scheduler.shed_victim(int(priority))
                if victim is None:
                    self.metrics.observe_rejected()
                    raise AdmissionRejected(self.scheduler.queue_depth,
                                            self.max_queue_depth)
                self._terminate(
                    victim, RequestState.FAILED,
                    f"shed under overload: queued at priority "
                    f"{victim.priority}, displaced by a priority "
                    f"{int(priority)} arrival")
                self.metrics.observe_shed()
            # "block": drain our own queue — each step admits/expires work,
            # and the queue head is guaranteed admissible once the pool
            # drains (submit validated it fits alone), so this terminates
            while self.has_work and \
                    self.scheduler.queue_depth >= self.max_queue_depth:
                self.step()
        rid = next(self._rid)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), stop_token=stop_token,
                      submit_time=time.perf_counter(),
                      deadline_s=deadline_s, max_queue_s=max_queue_s,
                      priority=int(priority),
                      migration_budget=(self.migration_budget
                                        if migration_budget is None
                                        else int(migration_budget)))
        req.trace_id = trace_id if trace_id else f"t{rid}"
        self.requests[rid] = req
        self.scheduler.submit(req)
        # keep /healthz honest between steps: an arrival bumps the cached
        # gauges immediately instead of waiting for the next commit
        self._health_gauges = {
            "queue_depth": self.scheduler.queue_depth,
            "num_running": len(self.scheduler.running),
            "step_latency_s": self._last_step_latency_s,
            "tier_blocks": len(self.kv_tier) if self.kv_tier is not None
            else 0,
            **self._gauge_extras}
        self.tracer.instant("serve.submit", trace=req.trace_id, rid=rid)
        return rid

    def cancel(self, rid: int, reason: str = "cancelled by client") -> bool:
        """Abort a queued or running request: frees its blocks, transitions
        it to CANCELLED. Returns False when the id is unknown or already
        terminal (cancel races are benign). ``reason`` lands in the
        request's structured error (e.g. "client disconnected")."""
        req = self.requests.get(rid)
        if req is None or req.state in TERMINAL_STATES:
            return False
        self._terminate(req, RequestState.CANCELLED, reason)
        return True

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def result(self, rid: int) -> Request:
        return self.requests[rid]

    def output_tokens(self, rid: int) -> List[int]:
        return list(self.requests[rid].out_tokens)

    def stats(self) -> Dict[str, Any]:
        """One flat dict: metrics summary + live engine/pool state and
        request-state counts (``requests_<state>``)."""
        s: Dict[str, Any] = dict(self.metrics.summary())
        states: Dict[str, int] = {st.value: 0 for st in RequestState}
        for r in self.requests.values():
            states[r.state.value] += 1
        s.update({f"requests_{k}": v for k, v in states.items()})
        s.update({
            "queue_depth": self.scheduler.queue_depth,
            "num_running": len(self.scheduler.running),
            "pool_free_blocks": self.pool.num_free,
            "pool_allocated_blocks": self.pool.num_allocated,
            "pool_evictable_blocks": self.pool.num_evictable,
            "prefix_cache_enabled": self.prefix_cache is not None,
            "prefix_indexed_blocks": (len(self.prefix_cache)
                                      if self.prefix_cache is not None else 0),
            "prefix_publish_suspended_now": (
                self.prefix_cache is not None
                and self.pool.occupancy > self.prefix_publish_max_occupancy),
            # the one serving path; the key stays because the benchmark
            # reads it (chipbench/drivers/serve_stdin.py:277-278)
            "decode_path": "paged",
            "compiled_step_signatures": len(self._jit),
            "step_seq": self.step_seq,
            "spec": self.spec_mode,
            "spec_k": self.spec_k if self.drafter is not None else 0,
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_token": self.pool.kv_bytes_per_token,
            "kv_scale_bytes_per_token": self.pool.kv_scale_bytes_per_token,
            "kv_lane_pack": self.pool.lane_pack,
            "quant_weights": self.quant_weights,
            "tp_degree": self.tp,
            "kv_bytes_per_token_per_shard":
                self._gauge_extras["kv_bytes_per_token_per_shard"],
            "sp_degree": self.sp,
            "pool_blocks_per_shard": self.pool.blocks_per_shard,
            "host_tier_enabled": self.kv_tier is not None,
        })
        # tier counters: live values when the tier exists, stable zeroed
        # keys otherwise (dashboards never see a shape change)
        s.update(self.kv_tier.stats() if self.kv_tier is not None else {
            "tier_blocks": 0, "tier_bytes": 0, "tier_max_bytes": 0,
            "tier_demotions": 0, "tier_demote_failures": 0,
            "tier_readmits": 0, "tier_corrupt_dropped": 0,
            "tier_evictions": 0})
        return s

    def check_invariants(self) -> None:
        """Pool bookkeeping + full block accounting against every running
        request's live table (only running requests hold blocks). Raises
        ValueError on any violation — the chaos suite's leak detector."""
        rows = [r for r in self.scheduler.running
                if r.block_table or r.summary_table or r.window_table]
        self.pool.check_invariants([r.block_table for r in rows],
                                   [r.cache_len for r in rows],
                                   [r.summary_table for r in rows],
                                   [r.window_table for r in rows],
                                   [r.window_base for r in rows])
        if self.pool.slots is not None:
            self.pool.slots.check_invariants(
                [r.state_slot for r in self.scheduler.running])
        if self.kv_tier is not None:
            self.kv_tier.check_invariants()

    def _terminate(self, req: Request, state: RequestState, error: str,
                   events: Optional[Dict[str, List]] = None,
                   bucket: Optional[str] = None) -> None:
        """Fault-isolation exit: free the request's blocks, move it to a
        terminal failure state, count it, and (when mid-step) report it in
        the step's event bucket."""
        now = time.perf_counter()
        if req.state is RequestState.QUEUED:
            req.queued_s += max(0.0, now - req.queued_time)
        else:
            self._note_leave_running(req, now)
        self._free_blocks(req)
        self.scheduler.terminate(req, state, error)
        self.tracer.instant("serve.terminal", trace=req.trace_id,
                            rid=req.rid, state=state.value,
                            step=self.step_seq)
        if state is RequestState.FAILED:
            self.metrics.observe_failed()
        elif state is RequestState.CANCELLED:
            self.metrics.observe_cancelled()
        elif state is RequestState.TIMED_OUT:
            self.metrics.observe_timeout()
        if events is not None and bucket is not None:
            events[bucket].append((req.rid, error))

    def _free_blocks(self, req: Request) -> None:
        """Give back everything a request holds: its exact pages and, in a
        windowed pool, its summary pages; both groups' of a pool of two."""
        held = req.block_table + req.summary_table + req.window_table
        if held:
            self.pool.free(held)
            req.block_table, req.summary_table = [], []
            req.window_table, req.window_base = [], 0
        if req.state_slot:
            self.pool.slots.free(req.state_slot)
            req.state_slot, req.snap_at = 0, [None, None]

    # -- state slots (kv_pool: State slots) -----------------------------------

    def _note_snapshots(self, rows, starts) -> None:
        """What a step just dispatched does to its rows' snapshots, by the
        rule the program follows on the device (``snapshot_slots``):
        a row it takes from a multiple of ``SNAPSHOT_EVERY`` has the state
        of that position in the snapshot of that turn from now on."""
        if self.pool.slots is None:
            return
        starts = np.asarray(starts[:len(rows)])
        keeps = snapshot_slots(
            np.array([req.state_slot for req in rows]), starts, np)
        for i in np.flatnonzero(keeps):
            # a row's two snapshots take turns: odd slots the even turns
            rows[i].snap_at[(keeps[i] + 1) % 2] = int(starts[i])
        self.metrics.observe_state_step(self.pool.slots.occupancy,
                                        int(np.count_nonzero(keeps)),
                                        self.pool.slots.nbytes)

    def _restore_fn(self):
        """``tnn_state_restore``: the named rows' snapshot into their live
        state, the one program a roll-back adds (beside ``tnn_kv_cow``)."""
        fn = self._jit.get("state_restore")
        if fn is None:
            def restore(arrays, slots, snaps):
                return kv_pool_lib.restore_state(arrays, slots, snaps)
            restore.__name__ = "tnn_state_restore"
            fn = self._jit["state_restore"] = jax.jit(restore,
                                                      donate_argnums=(0,))
        return fn

    def _restore_rows(self, rows) -> None:
        """After a chain was rolled back: every surviving row of it goes
        back to the newest snapshot at or before its committed length (the
        steps of the chain have advanced its live state past it), or to its
        first token where it has none (a row at position 0 starts from
        zeros), and pushes the committed tokens behind that position again
        as a prompt: the ordinary mixed step, whose page writes rewrite the
        same rows. A decoding row keeps its pending token, as a resumed one
        does."""
        b = self.scheduler.max_batch_size
        live, at_of = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
        n = replayed = 0
        for req in rows:
            if req.state is not RequestState.RUNNING or not req.state_slot:
                continue
            c = req.cache_len
            at = max((p for p in req.snap_at if p is not None and p <= c),
                     default=0)
            # a snapshot a rolled-back step took past the commit is of
            # tokens that were never committed
            req.snap_at = [p if p is not None and p <= c else None
                           for p in req.snap_at]
            if at:
                live[n], at_of[n] = req.state_slot, at
            n += 1
            replayed += c - at
            if at < c:
                if c >= req.prefill_len:    # it decodes: now it replays
                    self._note_leave_running(req, time.perf_counter())
                    req.phase, req.phase_t0 = "prefill", time.perf_counter()
                req.prefill_len = max(req.prefill_len, c)
                req.cache_len = at
                req.block_table = self.pool.truncate(req.block_table, at)
        if live.any():
            self.pool.state = self._restore_fn()(
                self.pool.state, self._put(live),
                self._put(snapshot_slots(live, at_of, np)))
        if n:
            self.metrics.observe_state_restore(n, replayed)

    # a request's tables, in the order ``_grow_need`` counts and ``_extend``
    # allocates them: every position's pages (a windowed pool: the current
    # window's), a windowed pool's summaries, a pool of two groups' window
    # pages
    _TABLES = ("block_table", "summary_table", "window_table")

    def _grow_need(self, req: Request, cache_len: int, new_tokens: int):
        """(exact, summary, window) blocks ``req`` lacks to write
        ``new_tokens`` positions from ``cache_len``."""
        need_e, need_s = self.pool.table_need(cache_len, new_tokens)
        need_w = self.pool.window_need(cache_len, new_tokens,
                                       req.window_base)
        return (max(0, need_e - len(req.block_table)),
                max(0, need_s - len(req.summary_table)),
                max(0, need_w - len(req.window_table)))

    def _extend(self, req: Request, grow) -> List[Any]:
        """Allocate ``grow`` = (exact, summary, window) blocks onto the
        request's tables; returns what a roll-back undoes: (req, table's
        attribute, the new blocks)."""
        done = []
        for attr, n in zip(self._TABLES, grow):
            if n:
                table = getattr(req, attr)
                ext = self.pool.alloc(n, start=len(table))
                done.append((req, attr, ext))
                table.extend(ext)
        return done

    def _unextend(self, done, *, only_intact: bool = False) -> None:
        """Undo extensions, youngest first: each is its table's TAIL (a
        window table may meanwhile have given pages back at its head)."""
        for req, attr, ext in reversed(done):
            table = getattr(req, attr)
            if table[len(table) - len(ext):] != ext:
                # a terminated/preempted row already freed its whole table
                # (extension included); only intact tables still own it
                if only_intact:
                    continue
                raise RuntimeError(f"{attr} of request {req.rid} no longer "
                                   "ends in the extension to roll back")
            self.pool.free(ext)
            del table[len(table) - len(ext):]

    def _pages_behind(self, req: Request, cache_len: int) -> int:
        """Logical pages at the head of ``req``'s window table that lie
        wholly behind the sliding window once ``cache_len`` positions are
        resident (0: one page group, or nothing to give back)."""
        if not (self.pool.sliding and req.window_table):
            return 0
        return max(0, self.pool.release_behind(cache_len) - req.window_base)

    def _window_ends(self, req: Request, cache_len: int) -> bool:
        """Whether ``cache_len`` resident positions end an exact window
        whose pages ``req`` still holds (a windowed pool)."""
        return bool(self.pool.window and req.block_table
                    and cache_len % self.pool.window == 0)

    def _end_window(self, req: Request) -> None:
        """A windowed request whose committed length reached a window's end
        gives its exact pages back: the next window starts with none, and
        reads this one through its summaries only. In a pool of two page
        groups, the window layers' pages that now lie wholly behind the
        window go back. (What a step packed ahead of this commit cannot
        know: ``_dispatch_ahead`` asks the same two questions.)"""
        drop = self._pages_behind(req, req.cache_len)
        if drop:
            n = drop * self.pool.window_layers
            self.pool.free(req.window_table[:n])
            del req.window_table[:n]
            req.window_base += drop
            self.metrics.observe_window_release(n)
            self.tracer.instant("serve.win_release", trace=req.trace_id,
                                rid=req.rid, step=self.step_seq,
                                at=req.cache_len, pages=n)
        if self._window_ends(req, req.cache_len):
            self.pool.free(req.block_table)
            req.block_table = []
            self.metrics.observe_eva_roll()
            self.tracer.instant("serve.eva_roll", trace=req.trace_id,
                                rid=req.rid, step=self.step_seq,
                                at=req.cache_len)

    def _attn_group(self, qw: int):
        """``(pages, heads)`` of a grid step of the step's attention kernel
        in the step program ``qw`` tokens wide: what the kernel's own launch
        derives from the same shapes (a TP shard's pool holds ``1 / tp`` of
        the heads; the kernel's "head" is a page row, ``kv_lane_pack`` KV
        heads side by side, attended by that many query groups). The paged
        kernel's, the latent kernel's, or a windowed model's
        (``tnn_eva_attention``, whose table is the exact segment and the
        summaries); None where the kernel runs over two groups of page with
        a walk of its own a kind of layer."""
        if self.pool.sliding:
            return None
        group = self._attn_groups.get(qw)
        if group is None:
            from ..ops.pallas.paged_attention import fetch_group
            pool, model = self.pool, self.model
            _, _, rows, bs, width = pool.page_shape
            tile, kw = qw, {}
            if pool.latent:     # the latent kernel's query tile and group
                from ..ops.pallas import mla_attention as mla
                tile = mla.query_tile(qw, model.num_heads)
                kw["positions"] = mla.GROUP_POSITIONS
            elif pool.window:
                from ..ops.pallas import eva_attention as eva
                kw["positions"] = eva.GROUP_POSITIONS
            group = self._attn_groups[qw] = fetch_group(
                bs=bs, dh=width, hkv=rows // self.tp,
                qg=tile * (model.num_heads // rows),
                page_dtype=(jnp.int8 if pool.kv_dtype == "int8"
                            else pool.dtype),
                nb=self.blocks_per_seq - (pool.slots is not None), **kw)
        return group

    def _observe_attention(self, rows, ends, qw: int, q_lens=None) -> None:
        """The attention counters of one paged step ``qw`` tokens wide whose
        row i's tokens end just before position ``ends[i]`` (``q_lens`` the
        launch's live tokens a row; None: the decode form, one tile wide).
        The fill of the page slots the kernel fetches in groups (a windowed
        model: over both segments of its table, the exact pages by the
        window-relative length and the summary pages by the summary rows
        read), and of a plain paged launch wider than one query tile the
        tiles the kernel computes over the tiles it holds. A windowed model
        besides: the share of the window's exact positions each row attends
        over, and the pool's rows that hold summaries."""
        pool = self.pool
        if q_lens is not None and not (pool.window or pool.latent):
            # the kernel module's own rule, at the launch's shape: the
            # queries are of the pool's compute dtype, a "head" is a page row
            from ..ops.pallas.paged_attention import (query_tile,
                                                      query_tiles_computed)
            g = self.model.num_heads // pool.page_shape[2]
            tile = query_tile(qw * g, pool.dtype)
            if tile < qw * g:
                self.metrics.observe_attn_query_tiles(
                    int(query_tiles_computed(q_lens, g, qw * g, tile).sum()),
                    len(q_lens) * qw * g // tile)
        group = self._attn_group(qw)
        if group is not None:
            pages, _ = group
            upto = np.asarray(ends[:len(rows)])
            if pool.window:
                # a step's tokens lie in one window: what ``_eva_paged``
                # hands the kernel as ``exact_lens`` and ``sum_lens``
                from ..ops.pallas.eva_attention import pages_fetched
                last = np.maximum(upto - 1, 0)
                live, slots = pages_fetched(
                    np.where(upto > 0, last % pool.window + 1, 0),
                    last // pool.window * (pool.window // pool.chunk),
                    pool.exact_width, pool.block_size, pages)
            else:
                live = -(-upto // pool.block_size)
                slots = -(-live // pages) * pages
            self.metrics.observe_attn_fetch(live[live > 0] / slots[live > 0])
        if pool.sliding:
            held = sum(len(r.window_table) for r in self.scheduler.running)
            self.metrics.observe_window_step(
                [min(int(e), pool.sliding) / pool.sliding
                 for _, e in zip(rows, ends)], held / pool.capacity)
        if not pool.window:
            return
        self.metrics.observe_eva_step(
            [((int(e) - 1) % pool.window + 1) / pool.window
             for _, e in zip(rows, ends)],
            sum(r.cache_len // pool.chunk for r in self.scheduler.running)
            / (pool.capacity * pool.block_size))

    def _observe_experts(self, experts, tokens: int) -> None:
        """A step's expert counters (``_stacked``), fetched with its
        ``tokens`` live tokens' samples (nothing for a model with no expert
        layer): held over all assignments, the held experts hit, the load's
        imbalance; with zero-compute experts, the picks that fell on them
        and the most real experts a token picked."""
        if experts:
            self.metrics.observe_experts(
                tokens, self.model.experts["top_k"],
                *(np.asarray(e) for e in experts))

    # -- per-request latency breakdown (host-side clocks only) ----------------

    def _note_admit(self, req: Request, now: float) -> None:
        """Close the request's queued clock at admission and open its
        prefill phase (the accumulators survive requeues — every QUEUED
        stretch adds up)."""
        wait = max(0.0, now - req.queued_time)
        req.queued_s += wait
        self.metrics.observe_queue_wait(wait)
        req.phase = "prefill"
        req.phase_t0 = now
        self.tracer.instant("serve.admit", trace=req.trace_id,
                            rid=req.rid, step=self.step_seq)

    def _note_prefill_done(self, req: Request, now: float) -> None:
        """Prompt fully resident: close the prefill clock, open decode."""
        if req.phase == "prefill":
            req.prefill_s += max(0.0, now - req.phase_t0)
        req.phase = "decode"
        req.phase_t0 = now

    def _note_leave_running(self, req: Request, now: float) -> None:
        """Close whichever phase clock is open — preemption, migration, or
        a terminal exit all end the RUNNING stretch the same way."""
        if req.phase == "prefill":
            req.prefill_s += max(0.0, now - req.phase_t0)
        elif req.phase == "decode":
            req.decode_s += max(0.0, now - req.phase_t0)
        req.phase = ""

    # -- engine step ----------------------------------------------------------

    def step(self) -> Dict[str, List]:
        """Run one serving step: expire deadlines, admit, then one mixed
        prefill+decode step.

        Returns the streamed increment this step produced::

            {"tokens":    [(rid, token), ...],
             "finished":  [rid, ...],
             "failed":    [(rid, error), ...],
             "timed_out": [(rid, error), ...]}

        Failures are isolated: a poisoned request (alloc failure, NaN
        logits, oversized resume, exhausted preemption budget) lands in
        ``failed`` and the rest of the batch keeps decoding.

        Every step also finalizes a flight-recorder record
        (``last_step_record``) — even when the step CRASHES, so a
        supervisor's post-mortem dump identifies the dying step's batch.

        A ``step()`` call is always synchronous: when a step is already in
        flight (an overlapped drive loop dispatched it) this finishes THAT
        step; otherwise it runs begin+finish back to back. Either way the
        deferred queue is drained before returning, so direct callers see
        the pre-overlap engine exactly.
        """
        if self._flight is None:
            self.begin_step()
        events = self.finish_step()
        self.run_deferred()
        return events

    def begin_step(self) -> "StepInFlight":
        """Build and DISPATCH one step without fetching its results:
        deadline expiry, scheduling, admissions, input staging (explicit
        ``device_put``) and the jitted launches all happen here; the one
        device->host fetch is deferred to ``finish_step``. Raises
        RuntimeError when a step is already in flight. A crash mid-build
        still finalizes the dying step's flight-recorder note."""
        if self._flight is not None:
            raise RuntimeError(
                "a step is already in flight — finish_step() first")
        self.step_seq += 1
        self._adopted_run = 0       # a step built anew: no run of adoptions
        fired_before = (Counter(self.faults.fired)
                        if self.faults is not None else None)
        # built BEFORE the step body runs: a crash fired at the very top of
        # the step (faults.on_step) must still leave a record naming the
        # batch it would have stepped
        note: Dict[str, Any] = {
            "step_seq": self.step_seq,
            "queued": self.scheduler.queue_depth,
            "running_rids": [r.rid for r in self.scheduler.running],
            "programs": [],
        }
        self._step_note = note
        flight = StepInFlight(self.step_seq, note, fired_before,
                              time.perf_counter())
        flight.gen_before = {
            r.rid: r.num_generated for r in self.scheduler.running
            if r.state is RequestState.RUNNING
            and r.cache_len >= r.prefill_len}
        self._open_phase("serve.build", "observe_build")
        try:
            with self._sync_guard():
                self._build_step(flight)
        except BaseException:
            self._finalize_note(flight)
            raise
        self._close_phase()         # a step that launched nothing
        self._flight = flight
        return flight

    def finish_step(self) -> Dict[str, List]:
        """Fetch the in-flight step's result bundle — the step's ONE
        ``jax.device_get`` — and run its commit phase: pool/scheduler
        state, stop and length checks, event buckets. Finalizes the step's
        flight-recorder note even when the commit crashes. Ends by
        resolving a speculatively dispatched successor (adopt or roll
        back), so afterwards ``in_flight`` is the adopted step or None."""
        flight = self._flight
        if flight is None:
            raise RuntimeError("no step in flight")
        try:
            with self._sync_guard():
                self._commit_step(flight)   # serve.commit opens at the fetch
        finally:
            flight.done = True
            self._flight = None
            self._finalize_note(flight)
            self._finished_note = flight.note
        self.metrics.observe_step_latency(flight.latency_s)
        self._last_step_latency_s = flight.latency_s
        # per-request stall attribution: a decode-phase row that survived the
        # step without committing a token spent the whole step stalled
        # (a retried fault, ...)
        for r in self.scheduler.running:
            if r.state is RequestState.RUNNING and \
                    r.num_generated == flight.gen_before.get(r.rid, -1):
                r.stall_s += flight.latency_s
        self._resolve_speculation(flight)
        self._close_phase()
        return flight.events

    def run_deferred(self) -> int:
        """Drain the deferred host-bookkeeping queue (prefix publishes and
        their tracing instants — work no commit depends on). The
        overlapped drive loops run this while the next step executes
        on-device; the synchronous ``step()`` drains it before returning,
        so overlap-off behavior is unchanged. Returns the items run."""
        if not self._deferred:
            return 0
        n = 0
        with self.tracer.span("serve.deferred", step=self.step_seq):
            while self._deferred:
                self._deferred.pop(0)()
                n += 1
        return n

    def _open_phase(self, name: str, observe: str) -> None:
        """Open one of the worker's phases that end somewhere else than
        they start (``serve.build`` ends at the step's first launch,
        ``serve.commit`` starts inside the fetch helper): a span in the
        tracer's sinks, and its length into the ``observe`` method of the
        metrics registry current when it closes. A phase a crash left open
        closes here, at the next one."""
        self._close_phase()
        sp = self.tracer.span(name, step=self.step_seq)
        sp.__enter__()
        self._phase = (sp, time.perf_counter(), observe)

    def _close_phase(self) -> None:
        if self._phase is not None:
            sp, t0, observe = self._phase
            self._phase = None
            getattr(self.metrics, observe)(time.perf_counter() - t0)
            sp.__exit__(None, None, None)

    def _dispatch(self, fn, kind: str, temps: np.ndarray, qw: int,
                  stage: Callable[[], tuple], ahead: int = 0):
        """``serve.dispatch``: the ENQUEUE of one compiled step program, not
        its compute (that is the device's, and shows as ``serve.fetch``), in
        its two halves: ``serve.put``, every ``device_put`` of the launch
        (``stage()`` returns the program's arguments behind the parameters
        and the pool), and ``serve.launch``, the jitted call alone (its
        arguments' flattening and the asynchronous enqueue). Their lengths
        feed ``observe_put`` / ``observe_launch``. ``ahead`` is how many
        uncommitted steps the program was dispatched behind (0: built after
        its predecessor's fetch); its ``step`` is the one it will commit as.
        What a span says of the PROGRAM (``_program_attrs``) is worked out
        once a compiled program. ``temps`` are the step's temperatures
        as packed: ``sampled_rows`` of them ask for a draw, and a step with
        none runs the sampler's argmax alone (``sampling.sample_ragged``)."""
        step = self.step_seq + ahead
        staged, self._put_pending = self._put_pending, 0.0
        sampled = int(np.count_nonzero(temps > 0.0))
        self.metrics.observe_step_dispatch(sampled)
        attrs = self._dispatch_attrs.get(fn)
        if attrs is None:
            attrs = self._dispatch_attrs[fn] = self._program_attrs(fn, qw)
        if self.pool.slots is not None:
            # the rows that hold a state slot, as of this dispatch
            attrs = dict(attrs, state_rows=self.pool.slots.rows
                         - self.pool.slots.num_free)
        with self.tracer.span("serve.dispatch", EventType.COMPUTE, step=step,
                              kind=kind, ahead=ahead, sampled_rows=sampled,
                              **attrs):
            t0 = time.perf_counter()
            with self.tracer.span("serve.put", step=step):
                args = stage()
            t1 = time.perf_counter()
            with self.tracer.span("serve.launch", EventType.COMPUTE,
                                  step=step):
                out = fn(self.params, self.pool.cache, *args)
            t2 = time.perf_counter()
        self.metrics.observe_put(staged + t1 - t0)
        self.metrics.observe_launch(t2 - t1)
        return out

    def _program_attrs(self, fn, qw: int) -> Dict[str, Any]:
        """What a paged step program ``qw`` tokens wide says of itself on
        its ``serve.dispatch``: its name (``program``: the device profile's
        ``XLA Modules`` line reads ``jit_<program>``, and the step-boundary
        reader checks its join of a program to the span that launched it
        against that); what a grid step of its attention kernel fetches
        (``attn_pages``, ``attn_heads``) and how many KV heads lie side by
        side in a page row of the pool (``kv_lane_pack``); a latent model's
        ONE page array, read once for keys and values (``latent_pages``);
        the routed experts this chip holds of each layer
        (``experts_held``)."""
        attrs: Dict[str, Any] = {"program": self._program_names.get(fn, "")}
        group = self._attn_group(qw)
        if group is not None and self.pool.latent:
            attrs["latent_pages"] = group[0]
        elif group is not None:
            attrs.update(attn_pages=group[0], attn_heads=group[1],
                         kv_lane_pack=self.pool.lane_pack)
        experts = getattr(self.model, "experts", None)
        if experts:
            attrs["experts_held"] = len(experts["held"])
        return attrs

    @property
    def in_flight(self) -> Optional["StepInFlight"]:
        """The dispatched-but-uncommitted step, when one is pending."""
        return self._flight

    def _finalize_note(self, flight: "StepInFlight") -> None:
        dt = time.perf_counter() - flight.t0
        flight.latency_s = dt
        note = flight.note
        note["step_latency_s"] = round(dt, 6)
        note["pool_allocated"] = self.pool.num_allocated
        note["pool_evictable"] = self.pool.num_evictable
        if flight.fired_before is None:
            note["faults_fired"] = {}
        else:
            note["faults_fired"] = {
                k: int(v - flight.fired_before.get(k, 0))
                for k, v in self.faults.fired.items()
                if v - flight.fired_before.get(k, 0)}

    def last_step_record(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder record of the most recent step: per-program kind
        + compile key + batch rids + fill, queue depth, pool/evictable
        occupancy, step latency, faults fired. None before the first step.
        A crashing step still finalizes its record — the last line of a
        supervisor crash dump is the step that died."""
        return dict(self._step_note) if self._step_note is not None else None

    def last_finished_record(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder record of the most recent FINISHED step. Under
        overlap the newest note (``last_step_record``) may belong to a
        still-in-flight — possibly speculative — step that a supervisor
        must not record yet; a crash dump still wants the newest."""
        return (dict(self._finished_note)
                if self._finished_note is not None else None)

    def _sync_guard(self):
        """``jax.transfer_guard("disallow")`` under TNN_DEBUG_SYNC=1: every
        implicit host<->device transfer inside the step raises.  _put and
        jax.device_get are explicit, so a clean step runs unchanged."""
        if self.debug_sync:
            return jax.transfer_guard("disallow")
        return nullcontext()

    def _put(self, x, dtype=None):
        """Explicit host->device transfer for step inputs (guard-proof
        replacement for the implicit jnp.asarray commit at dispatch).
        Under TP/SP the put replicates onto the mesh — a committed
        single-device array cannot feed a jit whose other operands live on
        the mesh."""
        if self._mesh is not None:
            return self._mesh.put_replicated(np.asarray(x, dtype))
        return jax.device_put(np.asarray(x, dtype), self.device)

    def _put_tables(self, tables):
        """Stage a step's GLOBAL block tables: a plain replicated put at
        sp=1 (and under TP — every shard holds every block), the stacked
        per-shard LOCAL view (``SPContext.put_tables``) under SP."""
        if self._sp is not None:
            return self._sp.put_tables(np.asarray(tables, np.int32),
                                       self.pool.blocks_per_shard)
        return self._put(tables, jnp.int32)

    def _put_block_id(self, blk, dtype=None):
        """Stage ONE global block id for the compiled whole-block write
        (adopt) step: a traced scalar at sp=1, a per-shard (1, 1) local
        table under SP — only the owner shard resolves a real row; everyone
        else sees ``-1`` and no-ops on its scratch page."""
        if self._sp is not None:
            return self._sp.put_tables(np.array([[blk]], np.int32),
                                       self.pool.blocks_per_shard)
        return self._put(blk, dtype)

    def _jit_step(self, name: str, fn, *, donate_argnums=()):
        """Compile a program body under a stable program ``name`` (the device
        profile's ``XLA Modules`` line reads ``jit_<name>``): plain jit at
        tp=sp=1, shard_map over the TP or SP mesh
        otherwise. A mesh is told what an argument IS by the body's
        parameter names, one convention for the step, copy-on-write and
        adopt programs: ``params``, ``cache`` (the pool's arrays as one
        donated pytree), under SP ``tables`` (the stacked per-shard block
        table or block id), the rest replicated; the body returns the cache
        between two groups of small results, ``(sampled, cache, counts)``
        (``_step_program`` says why in that order), each of which may be
        empty."""
        fn.__name__ = name
        jit = jax.jit if self._mesh is None else self._mesh.jit_step
        jitted = jit(fn, donate_argnums=donate_argnums)
        self._program_names[jitted] = name
        return jitted

    def _build_step(self, flight: "StepInFlight") -> None:
        """The build/dispatch phase: everything up to and including the
        jitted launches. The cache each launch returns is adopted at
        DISPATCH time (``_launch``) so the donation chain stays valid
        when another step is dispatched before this one's fetch."""
        events = flight.events
        if self.faults is not None:
            self.faults.on_step()
        self._enforce_deadlines(events)
        plan = self.scheduler.schedule(self.pool)
        chunks = dict(plan.chunks)
        for req in plan.prefills:
            if not self._admit_chunked(req, events):
                chunks.pop(req.rid, None)
            elif req.rid in chunks:
                # the grant was budgeted against the scheduler's cache
                # probe; clamp to the tail actually left after the fork
                # (a COW alloc fault may have fallen back to uncached)
                chunks[req.rid] = min(chunks[req.rid],
                                      req.prefill_len - req.cache_len)
        self._mixed_build(chunks, flight)

    def _commit_step(self, flight: "StepInFlight") -> None:
        """The commit phase: ONE batched fetch of the step's small
        sampled-token/ok/accepts bundle (never logits), then the minimal
        host bookkeeping that must precede building the next step.
        Deferrable work (prefix publishes) lands on ``self._deferred``."""
        events = flight.events
        if flight.recs:
            try:
                fetched = self._fetch_bundle(
                    [rec["dev"] for rec in flight.recs])
            except Exception as e:  # noqa: BLE001 — isolate, don't crash
                self._abort_flight(flight, f"step fetch failed: {e}")
                fetched = None
            if fetched is not None:
                for rec, out in zip(flight.recs, fetched):
                    self._commit_rec(rec, out, events)
        if not any(r.state is RequestState.RUNNING
                   and r.cache_len >= r.prefill_len
                   for r in self.scheduler.running):
            # no decode-phase rows left: the next decode token starts a new
            # stream, so the stall clock must not span the idle gap
            self._last_decode_emit = None
        tier_blocks = len(self.kv_tier) if self.kv_tier is not None else 0
        self.metrics.observe_gauges(self.scheduler.queue_depth,
                                    self.pool.occupancy,
                                    self.pool.kv_bytes_per_token,
                                    tp_degree=self.tp,
                                    sp_degree=self.sp,
                                    tier_blocks=tier_blocks,
                                    tier_bytes=(self.kv_tier.bytes_used
                                                if self.kv_tier is not None
                                                else 0.0))
        # host-side health gauges, cached at commit: /healthz answers from
        # the supervisor's copy without ever reaching into the engine
        self._health_gauges = {
            "queue_depth": self.scheduler.queue_depth,
            "num_running": len(self.scheduler.running),
            "step_latency_s": self._last_step_latency_s,
            "tier_blocks": tier_blocks,
            **self._gauge_extras}

    def _fetch_bundle(self, devs: List[Any]):
        """The step's single designated device->host fetch (the
        ``fetch-outside-commit`` lint rule pins every ``jax.device_get``
        on the step path to this helper): one batched transfer returns
        every launched program's sampled-token/ok/accepts bundle."""
        with self.tracer.span("serve.fetch", EventType.COMPUTE,
                              step=self.step_seq):
            out = jax.device_get(tuple(devs))
        self._t_fetch_done = time.perf_counter()
        self._open_phase("serve.commit", "observe_commit")
        return out

    def _abort_flight(self, flight: "StepInFlight", error: str) -> None:
        """Bundle-fetch failure: unattributable to one row, so every row
        the flight touched fails and the pool pages are recovered."""
        rows = [req for rec in flight.recs for req in rec["rows"]]
        self._abort_batch(rows, error, flight.events)

    def _mark_dispatch(self) -> None:
        """Stamp the step's first jitted launch: ``serve.build`` ends here,
        and the wall gap since the previous bundle fetch is the host gap
        the overlapped loop exists to close (in a profile: what lies
        between a ``serve.fetch`` and the next ``serve.dispatch``). First
        launch of a step consumes the stamp; speculative dispatches record
        a zero gap at adoption instead."""
        self._close_phase()
        t = self._t_fetch_done
        if t is None:
            return
        self._t_fetch_done = None
        gap = time.perf_counter() - t
        self.metrics.observe_host_gap(gap)
        for r in self.scheduler.running:
            if r.state is RequestState.RUNNING:
                r.host_gap_s += gap

    def _step_key(self):
        """The step's PRNG key: normally the next split, but a rebuild
        after an abandoned speculative dispatch REUSES the abandoned
        step's key, so the engine's key-consumption sequence (and thus
        every stochastic sample) matches the overlap-off engine exactly."""
        if self._reuse_keys:
            return self._reuse_keys.pop(0)
        return self._next_key()

    # -- speculative step pipelining ------------------------------------------

    def try_speculate(self) -> bool:
        """Speculatively build and dispatch one more step behind the step in
        flight (N) and the successors already dispatched: step N+j. Legal
        only when its build is fully determined by committed state plus the
        (unfetched) sampled tokens of the step before it, whatever that
        step's kind: a decode row is one token on and its next token is the
        step's sample, still on the device; a row pushing its prompt is its
        chunk on, and takes the chunk the scheduler's own arithmetic grants
        at that length (``Scheduler.plan_running``) or, its prompt done,
        decodes from its first sample (``_rows_after``). Every row must
        survive the j commits before it — no stop tokens, no deadlines,
        headroom for its next tokens, no chunk whose commit gives a
        window's pages back — with KV growth that fits the pool without
        preemption, no drafter, no fault plan, and a scheduler that would
        admit nothing at that step (``Scheduler.would_admit``: nobody
        waits, or every row is taken, or the head of the queue does not fit
        the budget or the pool; a server under load has a queue, and its
        rows are taken). The dispatched program reads its predecessor's
        sampled tokens as device-resident inputs (directly behind a decode
        step, through ``_splice_prev_tokens`` where rows moved or a prompt
        is in the step), so nothing syncs; ``finish_step`` validates the
        prediction
        and either adopts the oldest successor as the next in-flight step
        or rolls every one back (``_resolve_speculation``). Returns True
        when a step was dispatched: the drive loops call it until it says
        False (``_speculate_depth`` successors are dispatched); a False
        with a step in flight and depth to spare counts ONE reason
        (``ServingMetrics.observe_speculate_refusal``). Abandoned
        KV writes are harmless: they land at positions at or past every
        surviving row's committed length, or in blocks the rollback frees
        — always overwritten before attended."""
        flight = self._flight
        if (not self.overlap or flight is None or flight.done
                or len(flight.ahead) >= self._speculate_depth()):
            return False
        with self.tracer.span("serve.speculate",
                              step=self.step_seq + len(flight.ahead) + 1,
                              kind=self._newest_rec(flight).get("kind", "")):
            refusal = self._dispatch_ahead(flight)
        if refusal:
            self.metrics.observe_speculate_refusal(refusal)
        return not refusal

    @staticmethod
    def _newest_rec(flight: "StepInFlight") -> Dict[str, Any]:
        """The record of the newest step dispatched: the predecessor of the
        step ``try_speculate`` would dispatch ({}: nothing was launched)."""
        if flight.ahead:
            return flight.ahead[-1]["rec"]
        return flight.recs[-1] if flight.recs else {}

    def _grown_ahead(self, ahead) -> int:
        """Blocks the steps in ``ahead`` took for their rows' next tokens
        when they were dispatched: ``Scheduler.schedule`` plans a step
        BEFORE its rows grow, so it would still have found them
        allocatable. An upper bound under sequence parallelism (a shard's
        blocks count ``sp`` times in ``num_allocatable``): to expect an
        admission that does not come costs a rebuild, to miss one would
        cost the order of the steps."""
        return self.sp * sum(len(ext) for s in ahead
                             for _, _, ext in s["rollback"])

    def _running_rows(self) -> List[Request]:
        """The rows a step holds: RUNNING requests, in admission order."""
        return [r for r in self.scheduler.running
                if r.state is RequestState.RUNNING]

    def _rows_after(self, rec: Dict[str, Any]) -> Union[List[_RowAhead], str]:
        """The running rows, in the scheduler's order, as the commit of the
        dispatched step ``rec`` will leave them, or the refusal that says
        why that is not known. A step dispatched ahead starts from the
        prediction it was packed from (``rec["before"]``), a step that
        ``begin_step`` built from the committed state, which stands until
        its commit. ``_commit_rec`` restated: a
        decode row is one token on, its next token row i of the step's
        samples; a chunk row is its grant on, and at its prompt's end it
        decodes from row i too, or, resumed after a preemption, from the
        token it kept (``req.next_token``: that commit ignores the
        sample)."""
        after = rec.get("after")
        if after is not None:
            return after
        before = rec.get("before")
        if before is None:
            before = [_RowAhead(r, r.cache_len, r.num_generated,
                                _ON_HOST if r.cache_len >= r.prefill_len
                                else None) for r in self._running_rows()]
        rows, n_dec, takes = rec["rows"], rec["n_dec"], rec["takes"]
        at = {row.req.rid: k for k, row in enumerate(before)}
        if any(req.rid not in at for req in rows):
            return "other"              # a row left while the step flew
        after = list(before)
        for i, req in enumerate(rows):
            k = at.pop(req.rid)
            row = before[k]
            if i < n_dec:
                row = row._replace(cache_len=row.cache_len + 1,
                                   generated=row.generated + 1, src=i)
            else:
                row = row._replace(cache_len=row.cache_len + takes[req.rid])
                if row.cache_len >= req.prefill_len:
                    row = row._replace(src=_ON_HOST) if req.out_tokens \
                        else row._replace(generated=1, src=i)
            after[k] = row
        if at:
            # a running row the step does not hold: the budget left its
            # prompt no chunk, and what it gets next is not worked out here
            return "other" if any(before[k].decoding for k in at.values()) \
                else "mixed_step"
        rec["after"] = after
        return after

    def _dispatch_ahead(self, flight: "StepInFlight") -> str:
        """``try_speculate``'s body: "" when step N+j went out, else why
        not, by the name its refusal is counted under."""
        if self.faults is not None or self.drafter is not None:
            return "other"
        if len(flight.recs) != 1:
            return "other"              # the step launched nothing
        j = len(flight.ahead) + 1
        prev = self._newest_rec(flight)
        after = self._rows_after(prev)
        if isinstance(after, str):
            return after
        if [row.req for row in after] != self._running_rows():
            return "other"              # a row left while the chain flew
        pool = self.pool
        chunked = prev["takes"]             # the rows prev pushes a chunk of
        lens: Dict[int, int] = {}
        for row in after:
            req, at = row.req, row.cache_len
            # a decode row since ``since``: a step before this one ends its
            # window, and that commit gives the exact pages back, which no
            # prediction packs
            since = max(req.cache_len, req.prefill_len)
            if (req.stop_token is not None or req.deadline_s is not None
                    # a chunk whose commit changes the row's tables
                    # (``_end_window``)
                    or (req.rid in chunked
                        and (self._pages_behind(req, at)
                             or self._window_ends(req, at)))
                    or (row.decoding
                        and pool.room_in_window(since) <= at - since)):
                return "row_condition"
            if row.decoding and (row.generated >= req.max_new_tokens
                                 or at + 1 > self.max_seq_len):
                return "row_ends"
            lens[req.rid] = at
        chunks, _ = self.scheduler.plan_running(pool, lens)
        grows = []
        for row in after:
            take = 1 if row.decoding else chunks.get(row.req.rid, 0)
            if not take:
                return "mixed_step"     # a prompt the budget leaves no chunk
            grows.append(self._grow_need(row.req, row.cache_len, take))
        if self.scheduler.would_admit(pool, self._grown_ahead(flight.ahead),
                                      lens):
            return "admission"
        total = sum(map(sum, grows))
        if total and not pool.can_alloc(total):
            return "pool"
        rollback: List[Any] = []
        try:
            for row, g in zip(after, grows):
                rollback.extend(self._extend(row.req, g))
        except PoolExhausted:
            self._unextend(rollback)
            return "pool"
        # the step's rows as ``_mixed_build`` lays them: the decode rows,
        # then the rows that push a chunk, each in the scheduler's order.
        # Row i's token is row ``idx[i]`` of the predecessor's samples
        # where ``from_prev[i]``
        dec = [row for row in after if row.decoding]
        rows = [row.req for row in dec] + \
            [row.req for row in after if not row.decoding]
        b = self.scheduler.max_batch_size
        idx, from_prev = np.arange(b, dtype=np.int32), np.zeros(b, bool)
        for i, row in enumerate(dec):
            if row.src != _ON_HOST:
                idx[i], from_prev[i] = row.src, True
        try:
            rec = self._launch(
                "mixed" if len(dec) < len(rows) else "decode", rows, len(dec),
                {req.rid: chunks[req.rid] for req in rows[len(dec):]},
                lens=lens, splice=(prev["dev"][0], idx, from_prev), ahead=j)
        except _LaunchFailed as e:  # speculation must never hurt
            self._unextend(rollback)
            self._reuse_keys.insert(0, e.key)
            self._recover_pages_if_dead(flight.events)
            return "other"
        rec["before"] = after
        flight.ahead.append({
            "rec": rec, "rollback": rollback,
            # what its adoption holds the committed rows to: each one's
            # length, and with it whether it decodes or pushes its prompt
            "offsets": lens, "decoding": {row.req.rid for row in dec}})
        return ""

    def _warm_splice(self, shape) -> None:
        """Compile ``_splice_prev_tokens`` for a step program's token array
        when the program itself is made: a width's first step behind a
        prompt's chunk must find both compiled (warm-up reaches every
        width's program, not every width's chunk beside decoding rows)."""
        if not self.overlap:
            return
        b = shape[0]
        _splice_prev_tokens(
            self._put(np.zeros(shape, np.int32)),
            self._put(np.zeros((b,), np.int32)),
            self._put(np.arange(b, dtype=np.int32)),
            self._put(np.zeros((b,), bool)))

    def _speculate_depth(self) -> int:
        """Successor steps ``try_speculate`` keeps dispatched behind the
        step in flight: SPECULATE_AHEAD_S of device time at the last
        step's length, reached a step at a time while adoptions go on."""
        by_time = (SPECULATE_MAX if self._last_step_latency_s <= 0.0 else
                   -int(-SPECULATE_AHEAD_S // self._last_step_latency_s))
        return max(1, min(SPECULATE_MAX, by_time,
                          1 + self._adopted_run // SPECULATE_RAMP))

    def _resolve_speculation(self, flight: "StepInFlight") -> None:
        """After ``flight`` committed: adopt its oldest speculative
        successor when the prediction held (the same rows, each at the
        length it was packed at and so in the phase it was packed in,
        pushing its prompt or decoding, still running, and a scheduler that
        would still admit nothing: asked again of the committed state) and
        hand it the younger ones, else roll them all back — free the
        pre-grown blocks, stash the PRNG keys for reuse in order, and let
        the next ``begin_step`` rebuild from committed state. A row that
        failed alone, a cancellation, an arrival the scheduler admits: each
        changes the rows or what the scheduler says, and rolls back.

        An adopted step never runs ``begin_step``, so what that does for
        those who WAIT is done here, in its order: the queued requests'
        deadlines first (``_expire_waiting``, into the adopted step's
        events, the step boundary they would have had), then the
        scheduler's answer."""
        ahead, flight.ahead = flight.ahead, []
        if not ahead:
            return
        spec = ahead[0]
        rec = spec["rec"]
        running = self._running_rows()
        predicted = (
            [r.rid for r in running] == list(spec["offsets"])
            and all(req.cache_len == spec["offsets"][req.rid]
                    and (req.cache_len >= req.prefill_len)
                    == (req.rid in spec["decoding"]) for req in running))
        queued = self.scheduler.queue_depth
        timed_out: List[Any] = []
        if predicted and queued:
            self._expire_waiting({"timed_out": timed_out})
            predicted = not self.scheduler.would_admit(
                self.pool, self._grown_ahead(ahead))
        if not predicted:
            for s in reversed(ahead):
                self._unextend(s["rollback"], only_intact=True)
            if self.pool.slots is not None:
                self._restore_rows(running)
            self._reuse_keys[:0] = [s["rec"]["key"] for s in ahead]
            self._adopted_run = 0
            self.metrics.observe_overlap_rebuild()
            # no step to carry them: they end with the step that committed
            flight.events["timed_out"].extend(timed_out)
            return
        # prediction held: the dispatched step IS the next step — give it
        # its step_seq and flight-recorder note at adoption time. Its clock
        # starts here too: until now it waited behind its predecessor
        self._adopted_run += 1
        self.metrics.observe_adopted_step(rec["kind"])
        self.step_seq += 1
        note: Dict[str, Any] = {
            "step_seq": self.step_seq,
            "queued": queued,
            "running_rids": [r.rid for r in running],
            "programs": [dict(rec["prog"])],
            "speculative": True,
        }
        self._step_note = note
        rec["t0"] = time.perf_counter()
        nxt = StepInFlight(self.step_seq, note, None, rec["t0"])
        nxt.gen_before = {r.rid: r.num_generated for r in running
                          if r.cache_len >= r.prefill_len}
        nxt.events["timed_out"] = timed_out
        nxt.recs.append(rec)
        nxt.ahead = ahead[1:]
        self._flight = nxt
        # the dispatch preceded the fetch it would have waited for: the
        # adopted step's host gap is zero by construction
        self.metrics.observe_host_gap(0.0)
        self._t_fetch_done = None

    def _defer_publish(self, req: Request) -> None:
        """Queue a prefix-cache publish for the deferred phase. The
        snapshot is validated when it runs: the request must still be
        RUNNING with the snapshotted table prefix intact — a termination,
        preemption, or pool reset between commit and the deferred run
        makes the publish a silent no-op (its blocks may already be
        reused). A request that finishes NORMALLY in the same commit
        flushes its own queue first (``_flush_deferred_for``), so a
        short request's prefix is still indexed. Under overlap the index
        therefore lags the step stream by at most one step; matching is
        probe-only, so outputs are unaffected."""
        cache = self.prefix_cache
        tokens = req.resume_tokens
        clen = req.cache_len
        # the blocks the chunks so far FILLED, which is all a publish reads:
        # what steps dispatched ahead took for the chunks to come is theirs
        # to give back, and a roll-back must not cost the row its publish
        snap = req.block_table[:clen // self.pool.block_size]
        step = self.step_seq

        def run() -> None:
            if (req.state is not RequestState.RUNNING
                    or req.cache_len < clen
                    or req.block_table[:len(snap)] != snap):
                return
            cache.publish(tokens, snap, clen)
            self.tracer.instant("serve.publish", trace=req.trace_id,
                                rid=req.rid, step=step)

        run.rid = req.rid
        self._deferred.append(run)

    def _flush_deferred_for(self, req: Request) -> None:
        """Run this request's queued publishes NOW, ahead of the deferred
        phase. Called on the normal-finish path before the blocks are
        freed: the snapshot is still valid at this instant, but would be
        silently dropped by the deferred-phase guard once the pool
        reclaims the table (a request can fill its last block and finish
        inside the same commit)."""
        keep = []
        for fn in self._deferred:
            if getattr(fn, "rid", None) == req.rid:
                fn()
            else:
                keep.append(fn)
        self._deferred = keep

    def _enforce_deadlines(self, events: Dict[str, List]) -> None:
        self._expire_waiting(events)
        now = time.perf_counter()
        for req in list(self.scheduler.running):
            if req.deadline_s is not None and \
                    now - req.submit_time > req.deadline_s:
                self._terminate(
                    req, RequestState.TIMED_OUT,
                    f"deadline {req.deadline_s}s exceeded after "
                    f"{req.num_generated} tokens", events, "timed_out")

    def _expire_waiting(self, events: Dict[str, List]) -> None:
        """The queued requests' half of deadline expiry: it touches the
        wait queue and ``events["timed_out"]`` alone, so a step adopted
        without a ``begin_step`` runs it too (``_resolve_speculation``)."""
        now = time.perf_counter()
        for req in list(self.scheduler.waiting):
            if req.deadline_s is not None and \
                    now - req.submit_time > req.deadline_s:
                self._terminate(
                    req, RequestState.TIMED_OUT,
                    f"deadline {req.deadline_s}s exceeded while queued",
                    events, "timed_out")
            elif req.max_queue_s is not None and \
                    now - req.queued_time > req.max_queue_s:
                self._terminate(
                    req, RequestState.TIMED_OUT,
                    f"max_queue_s {req.max_queue_s}s exceeded",
                    events, "timed_out")

    def run_until_complete(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive steps until every submitted request finished; returns
        {rid: generated tokens}. With ``overlap`` on this is the
        overlapped drive loop: dispatch, speculate, deferred bookkeeping,
        then fetch+commit — a step stays in flight while the host works."""
        steps = 0
        while self.has_work or self._flight is not None:
            if self.overlap:
                if self._flight is None:
                    self.begin_step()
                while self.try_speculate():
                    pass
                self.run_deferred()
                self.finish_step()
            else:
                self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"no convergence after {max_steps} steps")
        self.run_deferred()
        return {rid: list(r.out_tokens) for rid, r in self.requests.items()
                if r.state is RequestState.FINISHED}

    # -- admission ------------------------------------------------------------

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        if self._mesh is not None:
            # jax.random.split runs on the default device; replicate the
            # subkey onto the mesh before it feeds a sharded step
            sub = self._mesh.put_replicated(sub)
        return sub

    def _admit_chunked(self, req: Request, events) -> bool:
        """Chunked admission: no device work — the request joins the running
        set immediately and its prompt is pushed chunk by chunk inside the
        mixed step (blocks are allocated per chunk, not up front). With the
        prefix cache on, the cached prefix is forked first — those
        positions are already-resident KV and are never prefilled."""
        nb_total = self.pool.table_width(req.prefill_len)
        if nb_total > self.blocks_per_seq:
            # unreachable via submit()'s validation (resume <= prompt +
            # max_new), but a corrupted resume must not poison the batch
            self._terminate(
                req, RequestState.FAILED,
                f"oversized resume: {req.prefill_len} tokens need "
                f"{nb_total} blocks > assembly capacity "
                f"{self.blocks_per_seq}", events, "failed")
            return False
        req.cache_len = 0
        if self.pool.slots is not None:
            # whatever the slot's last tenant left: a row at position 0
            # starts from zeros (GatedDeltaNet.apply_state)
            req.state_slot, req.snap_at = self.pool.slots.alloc(), [None, None]
        if self.prefix_cache is not None:
            self._match_prefix(req)
        self._note_admit(req, time.perf_counter())
        self.scheduler.admit(req)
        return True

    def _cow_copy_fn(self):
        def fn(cache, src, dst):
            # kv_pool.copy_blocks: under int8 the scale sidecar clones with
            # its pages, so the COW block dequantizes identically
            pages_k, pages_v = cache
            return (), (kv_pool_lib.copy_blocks(pages_k, src, dst),
                        kv_pool_lib.copy_blocks(pages_v, src, dst)), ()

        def sp_fn(cache, tables):
            # the clone was allocated on the SOURCE block's shard
            # (_match_prefix), so the copy is shard-local: the owner sees
            # (src_local, dst_local), every other shard sees (-1, -1) ->
            # clamped to its scratch page, a harmless identity write
            return fn(cache, jnp.maximum(tables[0, 0], 0),
                      jnp.maximum(tables[0, 1], 0))

        # donated + traced src/dst: one compile, in-place block copy
        return self._jit_step("tnn_kv_cow", fn if self._sp is None else sp_fn,
                              donate_argnums=(0,))

    def _demote_blocks(self, blocks: List[int]) -> None:
        """``pool.demote_hook``: salvage reclaimed-but-indexed blocks to
        the host tier before ``reclaim_hook`` unindexes them. ONE batched
        explicit ``jax.device_get`` fetches every demoted page slice (this
        runs on the allocation path, outside the step's fetch/commit
        machinery — the pool hook, not a step-path call). Best-effort
        throughout: an unindexed block, a tier-full bound, or an injected
        ``tier.demote_fail`` all degrade to the plain eviction that would
        have happened without a tier."""
        if self.kv_tier is None or self.pool.pages_deleted():
            return
        pairs = [(b, self.prefix_cache.key_of(b)) for b in blocks]
        pairs = [(b, k) for b, k in pairs if k is not None]
        if not pairs:
            return
        host = self.pool.export_blocks([b for b, _ in pairs])
        for (b, key), leaves in zip(pairs, host):
            if self.kv_tier.demote(key, leaves):
                self.tracer.instant("tier.demote", block=b,
                                    tier_blocks=len(self.kv_tier),
                                    tier_bytes=self.kv_tier.bytes_used)

    def _tier_adopt_fn(self):
        def fn(cache, blk, payload):
            # kv_pool.write_block: under int8 the payload is a QuantPages
            # of slices, so data and scales re-adopt together
            (pages_k, pages_v), (payload_k, payload_v) = cache, payload
            return (), (kv_pool_lib.write_block(pages_k, blk, payload_k),
                        kv_pool_lib.write_block(pages_v, blk, payload_v)), ()

        def sp_fn(cache, tables, payload):
            # handoff adopt under SP: the block id arrives as the per-shard
            # (1, 1) local view (_put_block_id) — the owner writes the
            # replicated payload into its row, every other shard writes it
            # into its scratch page (garbage-by-contract, never read)
            return fn(cache, jnp.maximum(tables[0, 0], 0), payload)

        # donated pages + traced block id: one compile serves every readmit
        return self._jit_step("tnn_tier_adopt",
                              fn if self._sp is None else sp_fn,
                              donate_argnums=(0,))

    def _tier_payload(self, leaves):
        """Demoted host leaves -> device payloads for the adopt fn:
        ``(k, v)`` plain arrays, or two QuantPages bundles from
        ``(k_data, k_scale, v_data, v_scale)`` under int8."""
        if len(leaves) == 4:
            return (kv_pool_lib.QuantPages(self._put(leaves[0]),
                                           self._put(leaves[1])),
                    kv_pool_lib.QuantPages(self._put(leaves[2]),
                                           self._put(leaves[3])))
        return self._put(leaves[0]), self._put(leaves[1])

    def _get_adopt_fn(self):
        """The compiled whole-block write step (one compile per pool
        dtype/TP signature serves every readmit and handoff adopt)."""
        adopt_key = ("tier_adopt",) + self._kv_key
        fn = self._jit.get(adopt_key)
        if fn is None:
            fn = self._jit[adopt_key] = self._tier_adopt_fn()
        return fn

    def _tier_readmit(self, seq) -> None:
        """Walk this prompt's chain keys and re-admit every demoted block
        the device index is missing: allocate a block, digest-verify the
        tier entry (``HostKVTier.verify_readmit`` — a corrupt entry frees
        the block again and the walk stops: an uncached miss), device_put
        the payload through the jitted adopt fn, index it
        (``prefix_cache.adopt``), and release it into the evictable LRU —
        from where the ordinary ``probe``/``fork`` revive path picks it up
        exactly as if it had never left the device. Allocation pressure
        (or an injected alloc fault) ends the walk early: the tier only
        ever adds hits."""
        readmitted = 0
        for key in self.prefix_cache.chain_keys(seq):
            if self.prefix_cache.contains_key(key):
                continue            # device-resident; deeper keys may tier
            if key not in self.kv_tier:
                break               # chain broken — nothing deeper can match
            try:
                blk = self.pool.alloc(1)
            except (PoolExhausted, FaultInjected):
                break
            if key not in self.kv_tier:
                # the alloc's own reclaim demoted blocks and LRU-displaced
                # this entry — an ordinary miss, not corruption
                self.pool.free(blk)
                break
            leaves = self.kv_tier.verify_readmit(key)
            if leaves is None:
                # corrupt/torn entry: dropped by the tier; degrade to miss
                self.metrics.observe_tier_corrupt()
                self.pool.free(blk)
                break
            payload_k, payload_v = self._tier_payload(leaves)
            self.pool.adopt_blocks([(blk[0], payload_k, payload_v)],
                                   self._get_adopt_fn(), self._put_block_id)
            self.prefix_cache.adopt(key, blk[0])
            # release into the evictable LRU (the block is now indexed):
            # probe() sees it immediately and fork() revives it — COW and
            # refcounts ride the unchanged device-hit machinery
            self.pool.free(blk)
            readmitted += 1
        if readmitted:
            self.metrics.observe_tier_hit(readmitted)
            self.tracer.instant("tier.readmit", blocks=readmitted,
                                tier_blocks=len(self.kv_tier),
                                tier_bytes=self.kv_tier.bytes_used)

    # -- cross-replica KV handoff (disaggregated serving) ---------------------

    def export_prefix(self, tokens: Sequence[int],
                      max_blocks: Optional[int] = None) -> List[tuple]:
        """Serialize the longest exportable chain prefix of ``tokens`` for
        cross-replica shipment: a list of ``(chain_key, leaves, digest)``
        wire blocks in chain order, where ``leaves`` is the host payload
        ``pool.export_blocks`` produces (int8 pools ship data + scale at
        ~half the f32 wire bytes) and ``digest = tier_digest(key, leaves)``
        — the receiver re-derives it from the wire bytes, so any in-flight
        damage is caught before a single page is written.

        Each key is sourced from the device index (``prefix_cache``) or
        from the host tier's staging buffer (``HostKVTier.peek`` —
        verified, non-destructive); the walk stops at the first key neither
        holds, since a chain with a hole cannot adopt past it. Best-effort
        and read-only: no refcounts move, nothing is consumed, an empty
        result just means the receiver recomputes."""
        if self.prefix_cache is None or self.pool.pages_deleted():
            return []
        # with the overlapped loop, publishes land on the deferred queue
        # and drain on idle time — a boundary export arriving right after
        # the first-token commit would find the chain this request JUST
        # prefilled still unpublished and degrade to recompute-resume.
        # Export runs on the engine's worker thread between ticks, which
        # is exactly where the deferred phase normally runs.
        self.run_deferred()
        keys = self.prefix_cache.chain_keys(tokens)
        if max_blocks is not None:
            keys = keys[:max_blocks]
        sources: List[tuple] = []      # (key, device block | None, leaves)
        for key in keys:
            blk = self.prefix_cache.block_of(key)
            if blk is not None:
                sources.append((key, blk, None))
                continue
            leaves = (self.kv_tier.peek(key)
                      if self.kv_tier is not None else None)
            if leaves is None:
                break
            sources.append((key, None, leaves))
        fetched = iter(self.pool.export_blocks(
            [b for _, b, _ in sources if b is not None]))
        exports = []
        for i, (key, blk, leaves) in enumerate(sources):
            if blk is not None:
                leaves = tuple(np.asarray(x) for x in next(fetched))
            if self.pool.sp > 1:
                # sp>1 wire tuples gain a 4th element: the context-mesh
                # shard that held this block's pages. A same-degree
                # receiver re-allocates on the matching shard so the
                # adopted chain keeps a balanced position->shard layout;
                # sp=1 stays a 3-tuple, byte-compatible with PR 19 peers.
                exports.append((key, leaves, tier_digest(key, leaves),
                                self.pool.owner(blk) if blk is not None
                                else i % self.pool.sp))
            else:
                exports.append((key, leaves, tier_digest(key, leaves)))
        if exports:
            self.metrics.observe_handoff_export(len(exports))
            self.tracer.instant("handoff.export", blocks=len(exports),
                                wire_bytes=sum(
                                    sum(x.nbytes for x in lv)
                                    for _, lv, _ in exports))
        return exports

    def _wire_leaves_ok(self, leaves) -> bool:
        """Geometry guard for wire payloads: a digest only proves the bytes
        match what the SENDER exported — a sender with a different pool
        geometry/dtype would still verify, then crash the adopt write. A
        mismatch degrades to recompute-resume, never an error."""
        shape = self.pool.page_shape[:1] + self.pool.page_shape[2:]
        if self.pool.kv_dtype == "int8":
            return (len(leaves) == 4
                    and leaves[0].shape == shape
                    and leaves[2].shape == shape
                    and leaves[0].dtype == np.int8
                    and leaves[2].dtype == np.int8
                    and leaves[1].shape == shape[:-1] + (1,)
                    and leaves[3].shape == shape[:-1] + (1,))
        return (len(leaves) == 2
                and leaves[0].shape == shape and leaves[1].shape == shape)

    def adopt_prefix(self, exports: Sequence[tuple]) -> int:
        """Adopt cross-replica wire blocks into this replica's prefix
        index; returns how many of the wire chain are RESIDENT afterwards
        (fresh adopts plus already-present dedupes — the caller's question
        is "will the resume prefix-hit here?", and a block this replica
        already holds answers it as well as a freshly written one; the
        ``handoff_adopted_blocks`` metric counts only real writes). Per
        block, in chain order: skip
        keys already resident; recompute ``tier_digest`` over the WIRE
        bytes and compare to the shipped digest (a mismatch — real damage
        or the seeded ``handoff.corrupt`` fault — drops the block and
        stops: the rest of the chain is unadoptable past a hole anyway);
        allocate a block (pool pressure ends the walk — handoff only ever
        adds hits); write the payload through the same compiled
        ``write_block`` step the host tier uses; index it
        (``prefix_cache.adopt``) and park it in the evictable LRU, from
        where the ordinary probe/fork machinery serves it exactly like
        locally-computed KV. Every degradation path returns a smaller
        count — the caller (router) falls back to token-exact
        recompute-resume, never a wrong token or a dropped request."""
        if self.prefix_cache is None or self.pool.pages_deleted():
            return 0
        adopted = resident = 0
        for i, ex in enumerate(exports):
            # PR 19 peers ship 3-tuples; sp>1 exporters append the owner
            # shard. Map it onto THIS replica's mesh degree (mod sp, the
            # degrees need not match), defaulting to chain-position
            # round-robin for legacy tuples.
            key, leaves, digest = ex[0], ex[1], ex[2]
            shard = (ex[3] if len(ex) > 3 else i) % self.pool.sp
            if self.prefix_cache.contains_key(key):
                resident += 1       # dedupe — served here, keep walking
                continue
            if self.faults is not None:
                if self.faults.handoff_slow():
                    # a congested transfer: the adopt succeeds, late
                    time.sleep(self.faults.handoff_slow_s)
                if self.faults.handoff_corrupt():
                    # flip one byte of a COPY so the digest check below
                    # catches planted damage exactly like real wire rot
                    leaves = tuple(np.array(x, copy=True) for x in leaves)
                    flat = leaves[0].reshape(-1).view(np.uint8)
                    flat[0] ^= 0xFF
            leaves = tuple(np.asarray(x) for x in leaves)
            if tier_digest(key, leaves) != digest:
                self.metrics.observe_handoff_corrupt()
                break
            if not self._wire_leaves_ok(leaves):
                break               # geometry mismatch — recompute instead
            try:
                blk = self.pool.alloc(1, start=shard)
            except (PoolExhausted, FaultInjected):
                break
            payload_k, payload_v = self._tier_payload(leaves)
            self.pool.adopt_blocks([(blk[0], payload_k, payload_v)],
                                   self._get_adopt_fn(), self._put_block_id)
            if not self.prefix_cache.adopt(key, blk[0]):
                # raced a local publish of the same chain: the key is
                # served either way; the private copy drains to free
                self.pool.free(blk)
                resident += 1
                continue
            # release into the evictable LRU (the block is now indexed):
            # probe() sees it immediately and fork() revives it
            self.pool.free(blk)
            adopted += 1
            resident += 1
        if adopted:
            self.metrics.observe_handoff_adopt(adopted)
            self.tracer.instant("handoff.adopt", blocks=adopted)
        return resident

    def prefix_keys(self) -> List[bytes]:
        """Chain keys this replica can currently export: the device index
        plus host-tier staged entries. The router's fleet-wide directory
        refreshes from this (content-addressed, so keys mean the same
        thing on every replica)."""
        if self.prefix_cache is None:
            return []
        keys = self.prefix_cache.keys()
        if self.kv_tier is not None:
            have = set(keys)
            keys.extend(k for k in self.kv_tier.keys() if k not in have)
        return keys

    def _match_prefix(self, req: Request) -> None:
        """Admission-time cache hit: fork the matched blocks into the
        request's table and mark their positions resident, so the chunked
        prefill pushes only the uncached tail.

        With a host tier, demoted prefix blocks are first re-admitted to
        the device (``_tier_readmit``) so the probe below sees them as
        ordinary evictable hits.

        A full-cover hit (``cow``) shares all but the last matched block
        and clones that one — the recomputed last prompt token writes its
        KV mid-block, and indexed blocks are immutable. If the clone's
        allocation fails (pool pressure or an injected fault), the forked
        references are released and the request admits uncached — a cache
        miss, never a failure."""
        seq = req.resume_tokens
        if self.kv_tier is not None and len(self.kv_tier):
            self._tier_readmit(seq)
        blocks, cached, cow = self.prefix_cache.probe(seq)
        self.metrics.observe_prefix_lookup(cached if blocks else 0, len(seq))
        if not blocks:
            return
        table = self.pool.fork(blocks[:-1] if cow else blocks)
        if cow:
            try:
                # under SP the clone must land on the SOURCE block's shard —
                # the jitted copy is shard-local (alloc's start is a table
                # position, so passing the owner index targets that shard)
                copy = self.pool.alloc(
                    1, start=(self.pool.owner(blocks[-1])
                              if self.pool.sp > 1 else 0))
            except (PoolExhausted, FaultInjected):
                if table:
                    self.pool.free(table)
                return
            cow_key = ("cow",) + self._kv_key
            fn = self._jit.get(cow_key)
            if fn is None:
                fn = self._jit[cow_key] = self._cow_copy_fn()
            if self._sp is not None:
                tail = (self._put_tables(
                    np.array([[blocks[-1], copy[0]]], np.int32)),)
            else:
                tail = (self._put(blocks[-1], jnp.int32),
                        self._put(copy[0], jnp.int32))
            _, self.pool.cache, _ = fn(self.pool.cache, *tail)
            table = table + copy
            self.metrics.observe_prefix_cow()
        req.block_table = table
        req.cache_len = cached

    # -- decode ---------------------------------------------------------------

    def _grow_blocks(self, req: Request, new_tokens: int, events,
                     *, chunk: bool) -> bool:
        """Grow ``req.block_table`` to cover ``cache_len + new_tokens``
        positions, preempting (LIFO) when the pool runs dry. Returns True
        when the row still runs this step; False when it was preempted,
        budget-FAILed (a victim that already spent its
        ``preemption_budget`` FAILs instead of requeueing: its freed blocks
        break the two-large-requests livelock), or hit an allocation fault
        — a chunk-boundary alloc
        failure fails ONLY this request (``chunk=True`` also routes the
        prefill fault-injection site at the boundary)."""
        need = self._grow_need(req, req.cache_len, new_tokens)
        grow = sum(need)
        while grow and not self.pool.can_alloc(
                grow, start=len(req.block_table)):
            victim = self.scheduler.preempt_victim()
            if victim is None or (victim is req
                                  and len(self.scheduler.running) == 1):
                # unreachable given submit()'s capacity validation
                raise RuntimeError(
                    "KV pool deadlock: no preemption victim can free "
                    "enough blocks")
            if self.preemption_budget is not None and \
                    victim.preemptions >= self.preemption_budget:
                self._terminate(
                    victim, RequestState.FAILED,
                    f"preemption budget exhausted "
                    f"({victim.preemptions} recompute preemptions >= "
                    f"budget {self.preemption_budget})",
                    events, "failed")
            else:
                self._preempt(victim)
            if victim is req:
                return False
        if req.state is not RequestState.RUNNING:
            return False
        try:
            if chunk and self.faults is not None:
                self.faults.on_prefill()
            self._extend(req, need)
        except (PoolExhausted, FaultInjected) as e:
            where = "at chunk boundary" if chunk else "mid-decode"
            self._terminate(req, RequestState.FAILED,
                            f"pool allocation failed {where}: {e}",
                            events, "failed")
            return False
        return True

    # -- mixed prefill+decode step --------------------------------------------

    def _mark_decode_emit(self) -> None:
        """Stamp a step that emitted decode-phase tokens; the gap between
        consecutive stamps is the decode stall chunking exists to bound."""
        now = time.perf_counter()
        if self._last_decode_emit is not None:
            self.metrics.observe_decode_stall(now - self._last_decode_emit)
        self._last_decode_emit = now

    def _propose_drafts(self) -> Dict[int, List[int]]:
        """Ask the drafter for up to ``spec_k`` lookahead tokens per
        decode-phase row. Each draft is clamped so the accepted prefix plus
        the verifier's bonus token can never overshoot ``max_new_tokens`` or
        the position cap; empty proposals are dropped (those rows ride the
        same step as plain single-token decode rows). Also routes the
        ``draft.poison`` chaos site — a corrupted draft must cost acceptance
        rate only, never output exactness.

        A drafter may return a host token list OR a
        ``spec_decode.DeviceDraft`` (device-resident, already
        vocab-clamped): device drafts never force a sync — their values
        are spliced into the step's token matrix on-device and come back
        to the host through the step's single fetch bundle."""
        drafts: Dict[int, Any] = {}
        vocab = self.model.vocab_size
        for req in self.scheduler.running:
            if req.state is not RequestState.RUNNING or \
                    req.cache_len < req.prefill_len:
                continue
            rem = req.max_new_tokens - req.num_generated
            k = min(self.spec_k, rem - 1,
                    self.max_seq_len - req.cache_len - 1)
            if k < 1:
                continue
            d = self.drafter.draft(req, k)
            if not isinstance(d, spec_decode.DeviceDraft):
                d = [int(t) % vocab for t in d][:k]
            elif self._mesh is not None:
                # the drafter runs single-device; replicate its tokens onto
                # the TP or SP mesh so the poison shift and the splice below
                # mix only mesh-resident arrays
                d = spec_decode.DeviceDraft(
                    self._mesh.put_replicated(d.toks))
            if not len(d):
                continue
            if self.faults is not None and self.faults.poison_draft():
                if isinstance(d, spec_decode.DeviceDraft):
                    d = d.shifted(self._put(1, jnp.int32),
                                  self._put(vocab, jnp.int32))
                else:
                    d = [(t + 1) % vocab for t in d]
            drafts[req.rid] = d
        return drafts

    def _mixed_build(self, chunks: Dict[int, int],
                     flight: "StepInFlight") -> None:
        """One packed step, build/dispatch half: every decode-phase running
        row takes 1 token and every mid-prefill row with a chunk grant
        pushes its next prompt chunk, all inside ONE compiled program keyed
        on the power-of-two bucket of the widest chunk. Steps with no chunk
        work run the pure-decode program. ``_commit_rec`` consumes the
        launch's fetched bundle.

        With a drafter installed, decode rows additionally carry their
        speculative lookahead as extra ragged positions (``q_len = 1 + k``)
        through the SAME launch; verification, accept/rollback, and the
        spec-off paths below stay byte-identical to the non-speculative
        engine for greedy requests."""
        events = flight.events
        t0 = time.perf_counter()
        spec_on = self.drafter is not None
        # drafts are proposed BEFORE the capacity pass so decode rows can
        # reserve KV headroom for every drafted position up front
        drafts = self._propose_drafts() if spec_on else {}
        # capacity pass in admission order: chunk rows grow by their grant
        # (the chunk-boundary alloc fault site — fails ONLY that request),
        # decode rows by one token plus their draft width, preempting LIFO
        # as needed. Under pool pressure speculation degrades FIRST: a draft
        # whose headroom is not free is shed before the row would have to
        # preempt a peer just to gamble on lookahead.
        for req in list(self.scheduler.running):
            if req.state is not RequestState.RUNNING:
                continue
            if req.cache_len < req.prefill_len:
                take = chunks.get(req.rid)
                if take and not self._grow_blocks(req, take, events,
                                                  chunk=True):
                    chunks.pop(req.rid, None)
            else:
                d = drafts.get(req.rid)
                if d:
                    grow = self.pool.blocks_for(
                        req.cache_len + 1 + len(d)) - len(req.block_table)
                    if grow > 0 and not self.pool.can_alloc(
                            grow, start=len(req.block_table)):
                        drafts.pop(req.rid, None)
                        d = None
                if not self._grow_blocks(req, 1 + (len(d) if d else 0),
                                         events, chunk=False):
                    drafts.pop(req.rid, None)
        live = self._running_rows()
        dec = [r for r in live if r.cache_len >= r.prefill_len]
        chk = [(r, chunks[r.rid]) for r in live
               if r.cache_len < r.prefill_len and r.rid in chunks]
        rows = dec + [r for r, _ in chk]
        if not rows:
            return
        kind = "spec" if spec_on else "mixed"
        if not chk and not any(drafts.get(r.rid) for r in dec):
            # nothing ragged this step: the pure-decode program is
            # bit-identical and cheaper (its clock starts here, behind the
            # capacity pass)
            kind, t0 = "decode", None
        try:
            rec = self._launch(kind, rows, len(dec),
                               {r.rid: t for r, t in chk}, drafts, t0=t0)
        except _LaunchFailed as e:
            # a real step failure may have consumed the donated pages:
            # unattributable, so the live batch aborts but the engine
            # survives for queued work
            self._abort_batch(rows, f"decode step failed: {e}", events)
            return
        if kind == "decode" and spec_on:
            rec["spec_rows"] = len(dec)     # the commit counts them
        flight.recs.append(rec)

    def _launch(self, kind: str, rows: List[Request], n_dec: int,
                takes: Dict[int, int], drafts=None, *,
                t0: Optional[float] = None, lens=None, splice=None,
                ahead: int = 0) -> Dict[str, Any]:
        """Pack ONE step and send it to the device; returns the record
        ``_commit_rec`` consumes. ``rows``: the ``n_dec`` decode rows (a
        token each, and their ``drafts``), then the rows that push
        ``takes[rid]`` tokens of their prompt; ``kind``: "decode" (no chunk,
        no draft: the decode program), "mixed" or "spec". In order: pack,
        the fault plan's poison, the step's note, the program from
        ``self._jit`` (or made, and its splice warmed), the token matrix of
        a synchronous mixed step, the step's key, the launch (retried once
        on a transient injected fault), the cache back into the pool at
        DISPATCH time, snapshots, attention counters, the record.

        A step dispatched ``ahead`` of its predecessors' commits
        (``_dispatch_ahead``) packs its rows at the predicted ``lens`` and
        finishes its tokens on the device from ``splice`` = (the
        predecessor's unfetched samples, ``idx``, ``from_prev``:
        ``_splice_prev_tokens``); it leaves the step's note and the host gap
        alone. A launch that raises comes out as ``_LaunchFailed`` with the
        step's key: what to do about it is the caller's."""
        if t0 is None:
            t0 = time.perf_counter()
        pool, spec_on = self.pool, kind == "spec"
        # pure host-side packing (compile-width bucketing, row layout,
        # compile key) lives in step_build; fault poisoning and dispatch
        # stay here with the rest of the device state
        packed = dict(
            b=self.scheduler.max_batch_size, nb=self.blocks_per_seq,
            scratch=PagedKVPool.SCRATCH, kv_key=self._kv_key,
            sum_at=pool.exact_width, kinds=pool.kinds,
            state=pool.slots is not None, lens=lens,
            on_device=() if splice is None else
            {rows[i].rid for i in np.flatnonzero(splice[2])})
        if kind == "decode":
            step = step_build.pack_decode(rows, **packed)
        else:
            step = step_build.pack_mixed(rows, n_dec, drafts or {}, takes,
                                         spec_on=spec_on, **packed)
        self._check_step_writes(step)
        b, qw, poison = step.b, step.qw, step.poison
        if self.faults is not None:
            if n_dec:
                poison[:n_dec][self.faults.poison_rows(n_dec)] = np.nan
            for i in range(n_dec, len(rows)):
                if self.faults.poison_prefill():
                    poison[i] = np.nan
        # the launched program in the step's flight record (a step
        # dispatched ahead gets its note at adoption)
        label = "decode_paged" if kind == "decode" else kind
        prog = {"kind": label, "compile_key": list(step.key),
                "rids": [r.rid for r in rows], "fill": round(len(rows) / b, 4)}
        if not ahead and self._step_note is not None:
            self._step_note["programs"].append(prog)
        fn = self._jit.get(step.key)
        if fn is None:
            fn = self._jit[step.key] = self._step_program(
                None if kind == "decode" else qw, spec_on)
            if not spec_on:
                self._warm_splice(step.toks.shape)
        toks = None
        if kind != "decode" and not ahead:
            # the token matrix is staged here, inside serve.build, because
            # the commit wants it back (``rec["dev"]``): a serve.put of its
            # own, counted into the step's put time at its launch
            t_put = time.perf_counter()
            with self.tracer.span("serve.put", step=self.step_seq):
                toks = self._put(step.toks)
                for i, dd in step.dev_drafts:
                    # splice device-resident drafts into the token matrix
                    # without fetching them. The commit reads draft VALUES
                    # back from the fetched token matrix, so host and device
                    # drafts commit identically. Under TP/SP the draft tensor
                    # (produced on the drafter's single device) replicates
                    # onto the mesh first — a device-to-device transfer, no
                    # host sync.
                    draft_toks = dd.toks if self._mesh is None \
                        else self._mesh.put_replicated(dd.toks)
                    toks = _splice_draft_row(toks, draft_toks[None, :],
                                             self._put(i, jnp.int32))
            self._put_pending = time.perf_counter() - t_put
        # one key per STEP (held across the retry): a transient fault retried
        # with the same key reproduces the fault-free step bit-for-bit
        step_key = self._step_key()
        if not ahead:
            self._mark_dispatch()

        def staged_toks():
            if toks is not None:
                return toks
            if splice is None:
                return self._put(step.toks)
            prev, idx, from_prev = splice
            if kind == "decode" and from_prev[:len(rows)].all() \
                    and (idx == np.arange(b)).all():
                return prev         # behind a decode step: its samples, as is
            dev = self._put(step.toks)
            if from_prev.any():
                dev = _splice_prev_tokens(dev, prev, self._put(idx),
                                          self._put(from_prev))
            return dev

        def stage():
            # a speculative program takes its rows' draft counts too
            return (staged_toks(), self._put(step.starts),
                    None if step.q_lens is None else self._put(step.q_lens),
                    self._put_tables(step.tables),
                    *((self._put(step.n_draft),) if spec_on else ()),
                    self._put(step.temps), self._put(step.topks),
                    self._put(step.topps), step_key, self._put(poison))

        for attempt in (0, 1):
            try:
                if self.faults is not None:
                    self.faults.on_decode()
                with self._sync_guard():
                    sampled, cache, counts = self._dispatch(
                        fn, label, step.temps, qw, stage, ahead=ahead)
                break
            except Exception as e:  # noqa: BLE001 — the caller isolates it
                # injected pre-call: donated buffers untouched, retryable
                if attempt == 0 and isinstance(e, FaultInjected) \
                        and e.transient:
                    self.metrics.observe_step_retry()
                    continue
                raise _LaunchFailed(e, step_key) from e
        pool.cache = cache
        self._note_snapshots(rows, step.starts)
        self._observe_attention(
            rows, step.starts + (1 if step.q_lens is None else step.q_lens),
            qw, step.q_lens)
        n_draft = getattr(step, "n_draft", None)    # a decode step has none
        return {"kind": kind,
                "dev": (*sampled, toks) if spec_on else (*sampled, *counts),
                "rows": list(rows), "n_dec": n_dec, "takes": takes,
                "n_draft": n_draft,
                "n_spec": 0 if n_draft is None else int(n_draft.sum()),
                "t0": t0, "b": b, "qw": qw, "key": step_key, "prog": prog}

    def _commit_rec(self, rec: Dict[str, Any], out, events) -> None:
        """A launched step's commit half, whatever its kind (a decode
        step's record is a mixed step's with every row a decode row and no
        ``takes``): consumes the fetched bundle —
        ``(accepts, newtok, ok, token_matrix)`` for spec steps (the token
        matrix carries the drafted values back, so device drafts never
        synced), ``(newtok, ok)`` and the expert counters otherwise."""
        spec_on = rec["kind"] == "spec"
        if spec_on:
            accepts, newtok, ok, toks_f = out
        else:
            newtok, ok, *experts = out
        rows = rec["rows"]
        takes = rec["takes"]
        if not spec_on:
            self._observe_experts(experts,
                                  rec["n_dec"] + sum(takes.values()))
        n_draft = rec["n_draft"]
        now = time.perf_counter()
        n_dec = rec["n_dec"]
        n_committed = 0
        for i, req in enumerate(rows):
            if req.state in TERMINAL_STATES:
                continue                # cancelled/expired while in flight
            if self.logit_guard and not bool(ok[i]):
                # poisoned row: only this request fails — its sampled token
                # is garbage and its KV blocks are freed; the other rows'
                # tokens in this very batch remain valid
                self._terminate(
                    req, RequestState.FAILED,
                    "non-finite logits in decode step" if i < n_dec
                    else "non-finite logits in prefill chunk",
                    events, "failed")
                continue
            if i < n_dec:
                if not spec_on:
                    tok = int(newtok[i])
                    req.cache_len += 1
                    self._end_window(req)
                    req.next_token = tok
                    req.out_tokens.append(tok)
                    events["tokens"].append((req.rid, tok))
                    self._maybe_finish(req, tok, events)
                    n_committed += 1
                    continue
                # accepted-prefix commit: replay the sequential emit for the
                # a accepted drafts plus the verifier's bonus/correction
                # token, stopping at the first finish exactly where
                # token-by-token decode would have stopped. Draft values
                # read back from the fetched token matrix.
                nd = int(n_draft[i])
                a = int(accepts[i])
                emitted = 0
                for tok in [int(x) for x in toks_f[i, 1:1 + a]] + \
                        [int(newtok[i])]:
                    req.cache_len += 1
                    req.next_token = tok
                    req.out_tokens.append(tok)
                    events["tokens"].append((req.rid, tok))
                    emitted += 1
                    self._maybe_finish(req, tok, events)
                    if req.state is not RequestState.RUNNING:
                        break
                self.metrics.observe_spec(nd, a, emitted)
                n_committed += emitted
                if req.state is RequestState.RUNNING and req.block_table:
                    # rejected-draft rollback: free the KV blocks past the
                    # committed length (slots past kv_len inside a kept
                    # block are garbage by contract and simply overwritten)
                    req.block_table = self.pool.truncate(
                        req.block_table, req.cache_len)
                continue
            take = takes[req.rid]
            req.cache_len += take
            self._end_window(req)
            self.metrics.observe_prefill_chunk(take)
            if self.faults is not None:
                self.faults.prefill_delay(take)
            self.tracer.instant("serve.prefill_chunk",
                                trace=req.trace_id, rid=req.rid,
                                step=self.step_seq, take=take)
            if self.prefix_cache is not None:
                # every block this chunk just FILLED is immutable now —
                # index it so the next shared-prefix request forks it.
                # Poisoned rows were terminated above, before cache_len
                # advanced, so their blocks are never published. Under pool
                # pressure publishing is suspended (degradation mode): a
                # bigger evictable set would just churn reclaims while live
                # requests are fighting for blocks. Matching stays on.
                # The suspension DECISION is taken at commit time; the
                # publish itself (index walk + hashing) is deferred off the
                # step critical path and re-validated when it runs.
                if self.pool.occupancy > self.prefix_publish_max_occupancy:
                    self.metrics.observe_publish_suspended()
                else:
                    self._defer_publish(req)
            if req.cache_len < req.prefill_len:
                continue            # more chunks to go; no token yet
            self._note_prefill_done(req, now)
            if req.out_tokens:
                # preemption recovery: the pending next_token survives; the
                # final chunk's own sample is redundant (greedy: identical)
                continue
            tok = int(newtok[i])
            req.next_token = tok
            req.out_tokens.append(tok)
            req.ttft_s = now - req.submit_time
            self.metrics.observe_ttft(req.ttft_s, under_load=n_dec > 0)
            self.tracer.instant("serve.first_token", trace=req.trace_id,
                                rid=req.rid, step=self.step_seq)
            events["tokens"].append((req.rid, tok))
            self._maybe_finish(req, tok, events)
        if rec["kind"] != "decode":
            self.metrics.observe_mixed_step(
                n_dec + rec["n_spec"] + sum(takes.values()),
                rec["b"] * rec["qw"])
        if n_dec:
            self._mark_decode_emit()
            self.metrics.observe_decode(
                n_committed if spec_on else n_dec,
                time.perf_counter() - rec["t0"], rec["b"])
        if rec.get("spec_rows"):
            # a spec-enabled step that proposed zero drafts ran the plain
            # decode program; its rows still count in the acceptance
            # denominator so spec stats stay honest
            self.metrics.observe_spec(0, 0, n_committed,
                                      rows=rec["spec_rows"])

    def _step_program(self, qw: Optional[int], spec: bool = False):
        """The engine's step programs: ``tnn_serve_decode`` (``qw`` None)
        and ``tnn_serve_mixed_w<qw>`` are ONE body, the decode program that
        body at one token a row (``q_lens`` None); ``tnn_serve_spec_w<qw>``
        has its own, for it judges every position. One signature: the
        params, the pool's ``cache`` (the ONE donated argument), the packed
        step's arrays, the sampling parameters, the step's key, the chaos
        plan's poison; the result is ``(sampled, cache, counts)``: the
        sampled tokens and their ``ok`` (a spec step's ``accepts`` before
        them), the cache, the expert counters (``_stacked``: none for a
        model with no expert layer). The leaves stand in the order they have
        had since the state and the counters came (PR 32, PR 44): the
        compiler's schedule follows the ORDER of a program's results, and a
        decode program with the cache first ran 0.1-0.4% slower in three
        closed cells (PERF.md section 6, PR 48). ``sampled`` and ``counts``
        are what the step's ONE fetch brings to the host."""
        model = self._step_model

        def fn(params, cache, toks, starts, q_lens, tables, t, k, p, key,
               poison):
            # the ragged paged-attention kernel takes decode rows (q_len 1)
            # and prompt chunks (q_len up to qw) in the same launch; dead
            # tokens scatter their KV to the scratch page and are masked.
            # No assembled cache: the model scatters each layer's new rows
            # into their pages and the kernel streams KV via the block
            # tables — per-step pool traffic is the row writes plus the KV
            # actually attended over. A model's state slots ride in the
            # cache beside the pages. The head runs on each row's last live
            # position alone: no (B, qw, V) cube of a wide step
            pages_k, pages_v, *state = cache
            decode = q_lens is None
            with moe_lib.collect_counts() as counts:
                logits, *cache = model.apply_paged(
                    params, toks[:, None] if decode else toks, pages_k,
                    pages_v, tables, starts, q_lens,
                    state=state[0] if state else None,
                    head_at=None if decode else jnp.maximum(q_lens - 1, 0))
            newtok, ok = _sampled(logits[:, 0], poison, key, t, k, p)
            return (newtok, ok), tuple(cache), _stacked(counts)

        def spec_fn(params, cache, toks, starts, q_lens, tables, n_draft,
                    t, k, p, key, poison):
            # the same ragged launch as the plain mixed step, but the FULL
            # (B, Q, V) logits cube feeds verification — every drafted
            # position is judged inside the one program
            logits, *cache = model.apply_paged(params, toks, *cache, tables,
                                               starts, q_lens)
            return self._spec_verify(logits, toks, q_lens, n_draft, t, k, p,
                                     key, poison), tuple(cache), ()

        if spec:
            return self._jit_step(f"tnn_serve_spec_w{qw}", spec_fn,
                                  donate_argnums=(1,))
        return self._jit_step(
            "tnn_serve_decode" if qw is None else f"tnn_serve_mixed_w{qw}",
            fn, donate_argnums=(1,))

    # -- speculative verification ----------------------------------------------

    @jax.named_scope("sample")
    def _spec_verify(self, logits, toks, q_lens, n_draft, t, k, p, key,
                     poison):
        """Token-exact verification of a ragged speculative step from the
        FULL ``(B, Q, V)`` logits cube.

        Row layout: ``toks[i] = [x_0, d_1..d_k, pad]`` with ``q_lens[i] =
        1 + n_draft[i]`` — position ``j``'s logits predict token ``j+1``, so
        drafted token ``toks[:, j+1]`` is judged by ``logits[:, j]``. Greedy
        rows (t<=0) accept the longest prefix where argmax matches the draft,
        byte-identical to token-by-token decode. Stochastic rows run exact
        rejection sampling: the drafters are DETERMINISTIC (propose with
        probability 1), so accepting ``d`` with probability ``p_target(d)``
        and re-drawing rejections from the residual — the target distribution
        with ``d`` masked out, renormalized — leaves the output distribution
        exactly the target's. Chunk rows (``n_draft = 0``) collapse to the
        plain last-live-position sample. Returns per-row
        ``(accepts, next_token, finite_ok)``."""
        logits = logits.astype(jnp.float32) + poison[:, None, None]
        B, Q, V = logits.shape
        pos = jnp.arange(Q)[None, :]
        is_live = pos < q_lens[:, None]
        ok = jnp.where(is_live[:, :, None],
                       jnp.isfinite(logits), True).all((-2, -1))
        greedy_tok = jnp.argmax(logits, axis=-1)                   # (B, Q)
        # drafted[:, j] = the token position j's logits must predict
        drafted = jnp.concatenate(
            [toks[:, 1:], jnp.zeros((B, 1), toks.dtype)], axis=1)
        is_draft = pos < n_draft[:, None]
        key_u, key_c = jax.random.split(key)
        filtered = sampling.filter_logits(logits, t[:, None], k[:, None],
                                          p[:, None])
        probs = jax.nn.softmax(filtered, axis=-1)
        p_draft = jnp.take_along_axis(probs, drafted[..., None],
                                      axis=-1)[..., 0]             # (B, Q)
        u = jax.random.uniform(key_u, p_draft.shape)
        match = jnp.where(t[:, None] > 0.0, u < p_draft,
                          greedy_tok == drafted) & is_draft
        accepts = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        # the bonus/correction token samples at the first unaccepted
        # position: a + (q_len - 1 - n_draft) is ``a`` for decode rows and
        # the last live position for chunk rows
        s = jnp.clip(accepts + q_lens - 1 - n_draft, 0, Q - 1)
        sel = jnp.take_along_axis(logits, s[:, None, None], axis=1)[:, 0]
        fsel = jnp.take_along_axis(filtered, s[:, None, None], axis=1)[:, 0]
        # rejection residual: mask the refused draft out of the target and
        # renormalize before the correction draw
        rejected = accepts < n_draft
        rej_tok = jnp.take_along_axis(
            toks, jnp.minimum(s + 1, Q - 1)[:, None], axis=1)[:, 0]
        res_mask = jnp.arange(V)[None, :] == rej_tok[:, None]
        fres = jnp.where(rejected[:, None] & res_mask, sampling.NEG_INF, fsel)
        newtok = jnp.where(t > 0.0,
                           jax.random.categorical(key_c, fres, axis=-1),
                           jnp.argmax(sel, axis=-1))
        return accepts, newtok, ok

    def _preempt(self, req: Request) -> None:
        self._note_leave_running(req, time.perf_counter())
        self._free_blocks(req)
        req.cache_len = 0
        self.scheduler.requeue(req)
        self.metrics.observe_preemption(req.rid)
        self.tracer.instant("serve.preempt", trace=req.trace_id,
                            rid=req.rid, step=self.step_seq)

    def _check_step_writes(self, step) -> None:
        """TNN_POOL_DEBUG=1: hold every packed step to the one-writer
        invariant the in-place page write relies on (``q_lens`` None: the
        decode form, one token a row)."""
        if self.pool.debug:
            # a pool with state slots: the last entry is no block
            tables = step.tables if self.pool.slots is None \
                else step.tables[:, :-1]
            self.pool.check_step_writes(
                tables, step.starts, np.ones_like(step.starts)
                if step.q_lens is None else step.q_lens)

    def _abort_batch(self, live: Sequence[Request], error: str,
                     events) -> None:
        """A decode failure that cannot be pinned on one row: fail every
        live request, then restore valid page buffers (a failed jitted call
        may have consumed the donated ones). Queued requests are untouched
        and re-prefill from scratch, so serving continues."""
        for req in live:
            if req.state is RequestState.RUNNING:
                self._terminate(req, RequestState.FAILED, error,
                                events, "failed")
        self._recover_pages_if_dead(events, force=True)

    def _recover_pages_if_dead(self, events, *, force: bool = False) -> None:
        """Re-zero the pool pages when a failed jitted step consumed the
        donated buffers (or unconditionally with ``force``, when no running
        request holds KV anyway). Any request still holding blocks at that
        point has lost its KV and must fail too."""
        dead = self.pool.pages_deleted()
        if not (dead or force):
            return
        ev = self.abort_all("KV pages lost to a failed step")
        for bucket in ("failed", "timed_out"):
            events[bucket].extend(ev[bucket])

    def abort_all(self, reason: str, *,
                  state: RequestState = RequestState.FAILED,
                  include_queued: bool = False,
                  reset_pages: bool = True) -> Dict[str, List]:
        """Supervisor-facing recovery: terminate every RUNNING request (and,
        with ``include_queued``, every QUEUED one) with the structured
        ``reason``, then — with ``reset_pages`` — re-zero the pool pages and
        drop the prefix index (re-zeroed pages no longer hold the indexed
        KV). The default leaves queued requests intact: a crash of the step
        loop only loses in-flight KV state, so queued work is salvageable
        and simply re-prefills after recovery.

        Returns step-shaped event buckets so callers can report the
        terminations the way ``step()`` would have."""
        # an in-flight or speculative step cannot survive recovery: its
        # device results are garbage once rows terminate (and pages reset),
        # and deferred publishes must never index reclaimed blocks
        self._flight = None
        self._deferred.clear()
        self._reuse_keys.clear()
        events: Dict[str, List] = {"tokens": [], "finished": [],
                                   "failed": [], "timed_out": []}
        bucket = "timed_out" if state is RequestState.TIMED_OUT else "failed"
        for req in list(self.scheduler.running):
            self._terminate(req, state, reason, events, bucket)
        if include_queued:
            for req in list(self.scheduler.waiting):
                self._terminate(req, state, reason, events, bucket)
        if reset_pages:
            self.pool.reset_pages()
            if self.prefix_cache is not None:
                # purge the evictable pool (reclaim_hook unindexes; the
                # demote hook is suppressed — zeroed pages must never be
                # salvaged) and drop any entries still covering
                # live-at-failure blocks
                self.pool.purge_evictable()
                self.prefix_cache.clear()
            if self.kv_tier is not None:
                # conservative: entries demoted before the failure derive
                # from pages we can no longer cross-check — drop them all
                self.kv_tier.clear()
            self._last_decode_emit = None
        return events

    def migrate_running(self, reason: str) -> Dict[str, List]:
        """Crash-survival re-admission: every RUNNING request loses its KV
        (the restart re-zeroes the pages) but NOT its progress — committed
        tokens ride along as an extended prompt through the scheduler's
        preemption-resume path, so the stream continues from the last
        emitted token, token-exact under greedy decoding. A request whose
        ``migration_budget`` is exhausted is FAILED instead: a poison
        request that keeps crashing the engine is isolated rather than
        wedging the restart loop. Pages are re-zeroed and the prefix index
        dropped exactly as in ``abort_all``.

        Returns step-shaped event buckets holding only the budget-exhausted
        terminations — migrated requests emit nothing; their streams simply
        continue after the re-prefill."""
        # same in-flight/deferred reset rationale as abort_all
        self._flight = None
        self._deferred.clear()
        self._reuse_keys.clear()
        events: Dict[str, List] = {"tokens": [], "finished": [],
                                   "failed": [], "timed_out": []}
        now = time.perf_counter()
        for req in list(self.scheduler.running):
            budget = req.migration_budget
            if budget is not None and req.migrations >= budget:
                self._terminate(
                    req, RequestState.FAILED,
                    f"migration budget exhausted ({budget}) — "
                    f"last failure: {reason}", events, "failed")
                continue
            self._note_leave_running(req, now)
            self._free_blocks(req)
            req.cache_len = 0
            self.scheduler.migrate(req)
            self.metrics.observe_migration(len(req.resume_tokens))
            self.tracer.instant("serve.migrate", trace=req.trace_id,
                                rid=req.rid, step=self.step_seq)
        self.pool.reset_pages()
        if self.prefix_cache is not None:
            self.pool.purge_evictable()
            self.prefix_cache.clear()
        if self.kv_tier is not None:
            # same conservative rule as abort_all: a crash mid-demote may
            # have captured torn pages, so nothing pre-crash may re-admit
            self.kv_tier.clear()
        self._last_decode_emit = None
        return events

    def _maybe_finish(self, req: Request, tok: int, events) -> None:
        if req.stop_token is not None and tok == req.stop_token:
            reason = "stop_token"
        elif req.num_generated >= req.max_new_tokens:
            reason = "length"
        else:
            return
        self._note_leave_running(req, time.perf_counter())
        if self._deferred:
            self._flush_deferred_for(req)
        self._free_blocks(req)
        self.scheduler.finish(req, reason)
        self.metrics.observe_finish(req.ttft_s)
        self.tracer.instant("serve.finish", trace=req.trace_id,
                            rid=req.rid, reason=reason,
                            step=self.step_seq)
        events["finished"].append(req.rid)
