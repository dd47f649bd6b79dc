"""Host-side step building: batch packing and compile keys, split from
pool/device state.

The engine's build/dispatch phase has two halves with different natures:

  1. PACKING — pure host math over scheduler grants and request records:
     lay rows into fixed-width arrays (tokens, offsets, block tables,
     sampling params), pick the compile-key bucket. No device state, no
     side effects.
  2. DISPATCH — device work: fetch-or-build the jitted program, feed it
     the pool's page buffers, adopt the donated pages it returns.

This module is half 1. Keeping it free of pool/device references is what
lets one packed step be dispatched unchanged to any device topology: at
tp=1 the arrays feed a plain ``jax.jit`` program; under tensor parallelism
the SAME packed step is dispatched per-shard via ``shard_map`` (every
shard receives the identical replicated batch and sweeps its own head
shard of the pool — serving/tp.py). The packed batches are also what the
engine's step-program notes record, so they double as the replay surface.

Row layout contract (mirrored by the commit halves in engine.py):
``pack_mixed`` puts decode-phase rows first (each carrying 1 committed
token plus optional speculative draft positions), then mid-prefill chunk
rows; ``pack_decode`` is the pure-decode batch, one token per row.
Padding rows point their tables at the pool's scratch block.

Both pack a step AHEAD of its predecessors' commits too (the overlapped
engine's predicted step): ``lens`` gives each row's predicted length in
place of its committed ``cache_len``, and the rows named in ``on_device``
leave their pending token zero, because it is an unfetched sample of the
step before: the engine finishes the token array on the device.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Collection, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops.pallas.paged_attention import group_segments
from ..utils.bucketing import pow2_bucket
from . import spec_decode


@dataclasses.dataclass
class PackedStep:
    """One step's host-side arrays + compile key. ``poison`` starts zeroed;
    the engine's fault plan may NaN rows in place before dispatch (chaos
    injection is deliberately outside the pure packing math)."""
    key: Tuple[Any, ...]            # jit-cache compile key
    tables: np.ndarray              # (B, nb) block tables, scratch-padded
    temps: np.ndarray               # (B,) sampling temperature per row
    topks: np.ndarray               # (B,) top-k per row
    topps: np.ndarray               # (B,) top-p per row
    poison: np.ndarray              # (B,) f32 additive logit poison (chaos)
    b: int                          # compiled batch width
    nb: int                         # compiled table width (blocks per seq)
    toks: np.ndarray = None         # (B, qw) token matrix; decode form: (B,)
    starts: np.ndarray = None       # (B,) first write position per row
    # (B,) live tokens per row; None: the decode form, one token a row
    q_lens: Optional[np.ndarray] = None
    qw: int = 1                     # compiled chunk width (pow2 bucket)


@dataclasses.dataclass
class MixedStep(PackedStep):
    """The ragged mixed prefill+decode batch (optionally speculative)."""
    n_draft: np.ndarray = None      # (B,) drafted lookahead per decode row
    # (row index, DeviceDraft) pairs whose tokens splice in on-device
    dev_drafts: List[Tuple[int, Any]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class DecodeStep(PackedStep):
    """The pure-decode batch: one committed token per row, ``starts`` its
    kv length before this token."""


def shard_tables(tables: np.ndarray, sp: int,
                 blocks_per_shard: int) -> np.ndarray:
    """GLOBAL block tables -> stacked per-shard LOCAL tables for sequence
    parallelism. Pure host math — the dispatch side stages the result over
    the context mesh with ``P("seq", None, None)``.

    ``tables``: global ids of any rank — (B, nb) step tables, the (1, k)
    block-id pairs of the COW/adopt steps.
    Position j's block was allocated from shard ``j % sp``
    (``PagedKVPool.alloc(..., start=)``) but this function derives
    ownership from the ID RANGE, ``g // blocks_per_shard``, so COW-forked
    and handoff-adopted blocks land on whichever shard actually holds
    their pages. Returns (sp, *tables.shape) int32 where shard s's entry
    is the LOCAL row ``g % blocks_per_shard`` if shard s owns ``g``, else
    ``-1``: the paged kernel skips -1 blocks and the page write redirects
    them to the shard's scratch page.
    """
    owner = tables // blocks_per_shard
    local = (tables % blocks_per_shard).astype(np.int32)
    shards = np.arange(sp, dtype=np.int32).reshape(
        (sp,) + (1,) * tables.ndim)
    return np.where(owner[None] == shards, local[None],
                    np.int32(-1))


def _fill_row(step: PackedStep, i: int, req, sum_at: int = 0,
              kinds=None, state: bool = False) -> None:
    """``state``: a pool with state slots (``kv_pool``: State slots): the
    row's LAST entry is its slot (a padding row's: the scratch slot 0).
    ``sum_at``: where a windowed pool's summary pages start in the packed
    table (its exact segment's width, ``PagedKVPool.exact_width``).
    ``kinds``: a pool of two page groups (``PagedKVPool.kinds``: global
    layers, window layers, entries a window layer): the row is a segment a
    global layer, a segment a window layer, then the window table's base."""
    if kinds:
        n_full, n_win, ww = kinds
        row = step.tables[i]
        _, at_full, at_win = group_segments(len(row), n_full, n_win, ww)
        for table, n, starts in ((req.block_table, n_full, at_full),
                                 (req.window_table, n_win, at_win)):
            for j, at in enumerate(starts):
                seg = table[j::n]
                row[at:at + len(seg)] = seg
        row[-1] = req.window_base
    else:
        step.tables[i, :len(req.block_table)] = req.block_table
        if state:
            step.tables[i, -1] = req.state_slot
    if req.summary_table:
        step.tables[i, sum_at:sum_at + len(req.summary_table)] = \
            req.summary_table
    step.temps[i] = req.temperature
    step.topks[i] = req.top_k
    step.topps[i] = req.top_p


def _alloc_common(b: int, nb: int, scratch: int):
    return dict(
        tables=np.full((b, nb), scratch, np.int32),
        temps=np.zeros((b,), np.float32),
        topks=np.zeros((b,), np.int32),
        topps=np.zeros((b,), np.float32),
        poison=np.zeros((b,), np.float32))


def pack_mixed(rows: Sequence[Any], n_dec: int, drafts: Dict[int, Any],
               takes: Dict[int, int], *, b: int, nb: int, scratch: int,
               spec_on: bool, kv_key: Tuple[Any, ...],
               sum_at: int = 0, kinds=None, state: bool = False,
               lens: Optional[Dict[int, int]] = None,
               on_device: Collection[int] = ()) -> MixedStep:
    """Pack decode rows (first ``n_dec`` of ``rows``, each 1 token +
    optional draft) and prompt-chunk rows (the rest, ``takes[rid]`` tokens
    each) into one ragged batch. Host drafts land in the token matrix here;
    ``DeviceDraft`` rows are recorded in ``dev_drafts`` for the engine to
    splice on-device (their values never touch the host). ``lens`` /
    ``on_device``: the predicted step (module docstring); a chunk row's
    chunk starts at its predicted length."""
    widest = max([takes[r.rid] for r in rows[n_dec:]]
                 + [1 + len(drafts.get(r.rid, ())) for r in rows[:n_dec]])
    qw = pow2_bucket(widest)
    key = (("mixed", b, qw, nb, "spec") if spec_on
           else ("mixed", b, qw, nb)) + kv_key
    step = MixedStep(
        key=key, b=b, nb=nb, qw=qw,
        toks=np.zeros((b, qw), np.int32),
        starts=np.zeros((b,), np.int32),
        q_lens=np.zeros((b,), np.int32),
        n_draft=np.zeros((b,), np.int32),
        **_alloc_common(b, nb, scratch))
    for i, req in enumerate(rows):
        start = step.starts[i] = lens[req.rid] if lens else req.cache_len
        _fill_row(step, i, req, sum_at, kinds, state)
        if i < n_dec:
            d = drafts.get(req.rid, []) if spec_on else []
            if req.rid not in on_device:
                step.toks[i, 0] = req.next_token
            if isinstance(d, spec_decode.DeviceDraft):
                step.dev_drafts.append((i, d))
            elif d:
                step.toks[i, 1:1 + len(d)] = d
            step.q_lens[i] = 1 + len(d)
            step.n_draft[i] = len(d)
        else:
            take = takes[req.rid]
            seq = req.resume_tokens
            step.toks[i, :take] = seq[start:start + take]
            step.q_lens[i] = take
    return step


def pack_decode(live: Sequence[Any], *, b: int, nb: int, scratch: int,
                kv_key: Tuple[Any, ...], sum_at: int = 0, kinds=None,
                state: bool = False,
                lens: Optional[Dict[int, int]] = None,
                on_device: Collection[int] = ()) -> DecodeStep:
    """Pack the pure-decode batch. ``lens`` / ``on_device``: the predicted
    step (module docstring): each row's start is its predicted length, and
    a row whose token is on the device leaves it zero. Where that is every
    row, in their predecessor's order, the dispatched program reads its
    predecessor's unfetched sampled tokens directly as its input."""
    step = DecodeStep(
        key=("pdecode", b, nb) + kv_key, b=b, nb=nb,
        toks=np.zeros((b,), np.int32),
        starts=np.zeros((b,), np.int32),
        **_alloc_common(b, nb, scratch))
    for i, req in enumerate(live):
        if req.rid not in on_device:
            step.toks[i] = req.next_token
        step.starts[i] = lens[req.rid] if lens else req.cache_len
        _fill_row(step, i, req, sum_at, kinds, state)
    return step
