"""Block-based paged KV-cache pool (vLLM-style, arXiv:2604.15464's storage
model) for continuous-batching inference.

The pool owns two device arrays of fixed-size token pages per layer,

    pages_k, pages_v : (L, num_blocks, H_kv / p, block_size, p * head_dim)

(``page_shape``; ``p`` = ``lane_pack`` KV heads side by side in a page row)
plus host-side bookkeeping: a free list, and a per-block refcount so a shared
prompt prefix can be forked (``fork``) instead of copied. Sequences hold a
*block table* — an ordered list of block ids — which the paged-attention
kernel and the page write (``ops/pallas/paged_attention.py``) take as it is:
nothing ever assembles a contiguous cache.

Block 0 is RESERVED as scratch: padded rows of a ragged batch (the engine
always decodes at a fixed batch width) point their block tables at it, so
their garbage reads/writes land somewhere harmless instead of in live blocks.

The pages stay device-resident; only the alloc/free bookkeeping lives on the
host. The whole-page helpers at the bottom (``write_block``, ``copy_blocks``)
are pure jnp functions that trace into the engine's COW and adopt steps.

Page layout contract
--------------------
The ragged paged-attention kernel (``ops/pallas/paged_attention.py``) reads
the pages *directly* — no gather — so the layout below is a cross-module
contract, not an implementation detail:

- A sequence's cache position ``t`` lives at
  ``pages_*[layer, table[t // block_size], kv_head // p, t % block_size,
  (kv_head % p) * Dh : (kv_head % p + 1) * Dh]``:
  positions are contiguous within a block and ordered across the block
  table, while the blocks themselves may sit anywhere in the pool.
- ``p`` (``lane_pack``, from ``ops.pallas.paged_attention.lane_pack``) is
  the largest divisor of the KV heads one device holds that is at most
  ``128 // Dh``: a page row then fills the 128 lanes and the pool rests in
  the layout the kernel and the page write read (at ``Dh`` = 64 an
  unpacked pool was converted in and out by every step program). 1 at
  ``Dh`` >= 128, for an odd head count, for int8 pages and for a windowed
  pool, which is the plain ``(L, N, H_kv, bs, Dh)``. Whoever needs the
  shape asks ``page_shape``; whole-page payloads (``export_blocks``,
  ``write_block``, ``copy_blocks``) move ``(L, H_kv / p, bs, p * Dh)``.
- Block tables handed to the kernel are right-padded with ``SCRATCH``
  (``padded_table``); the kernel clamps its page fetches to each row's last
  live block, so padding entries are never DMA'd on TPU.
- Token position ``p`` is live iff ``p < kv_len`` for that row; slots past
  ``kv_len`` (the block's tail, scratch writes of padded rows) hold garbage
  by design and every consumer must mask them.
- Pages are stored in the pool dtype (the model's compute dtype); the
  engine donates them through every jitted step, so after a step the
  previously-held arrays are invalid — always re-read ``pool.pages_*``.
- With ``kv_dtype="int8"`` each ``pages_*`` is a ``QuantPages`` bundle:
  int8 ``data`` in the layout above (``p`` = 1) plus a per-(position, head)
  f32 ``scale`` sidecar of shape ``(L, N, H_kv, bs, 1)``. The bundle is a
  pytree, so it rides through every jitted step, donation, and
  ``update_pages`` as one value — scales can never be re-adopted without
  their pages or vice versa. Rows are quantized at scatter time and
  dequantized at the attention read; the block-table math is identical,
  so fork/COW/truncate/eviction never look inside the bundle.

Two kinds of page
-----------------
A model whose attention keeps an exact window beside learned summaries (EVA:
``ops/pallas/eva_attention.py``) holds two kinds of state, and the pool holds
both in the SAME arrays, from ONE allocator and under ONE occupancy. With
``window`` and ``chunk`` set, a request has two tables:

- its *exact* table (``Request.block_table``): the pages of the positions of
  its CURRENT window only, position ``p`` at window-relative position
  ``p % window``. It grows as the window fills and is released whole when the
  window ends (``cache_len`` reaches a multiple of ``window``);
- its *summary* table (``Request.summary_table``): row ``c`` holds the
  summary ``(k~, v~)`` of tokens ``chunk * c .. chunk * c + chunk - 1``. It
  only grows, and goes when the request does.

A step's packed table is ``window // block_size`` exact entries, then the
summary entries (``table_width``). ``window = None`` is the case "every
position exact, no summaries": GPT-2's, with the exact table the whole
table. ``table_need`` / ``lifetime_blocks`` answer for both kinds, and
``check_invariants`` / ``check_step_writes`` know both.

Two page groups
---------------
A model of sliding-window layers beside global layers (``models.llama``'s
``gated``) keeps two groups of page, from the SAME array, ONE allocator and
ONE occupancy. With ``groups`` set (``window``, ``full_layers``,
``window_layers``) the array has one layer, ``(1, N, H_kv / p, bs, p * Dh)``,
and a block is one LAYER's page of one group; a request has two tables:

- its *global* table (``Request.block_table``): ``full_layers`` blocks for
  every page of its context, entry ``i * full_layers + j`` global layer
  ``j``'s page of positions ``i * bs ..``. It only grows;
- its *window* table (``Request.window_table``): ``window_layers`` blocks for
  every page it still holds, entry 0 the logical page
  ``Request.window_base``. A page wholly behind ``position - window`` is
  given back (``release_behind``), so a row never holds more than
  ``win_pages`` = ``window / bs + 2`` of them however long it grows.

With one table for all five layers a 37 k context would hold 290 pages in
each of them; here four of the five hold 34. A step's packed table is a
segment a global layer, a segment of ``win_pages`` a window layer, then
``window_base`` (``table_width``; ``step_build._fill_row`` packs it,
``Llama._paged_layers`` splits it). Admission plans both groups to the
request's last token (``lifetime_blocks``), as the windowed pool above.

Latent pages
------------
A model with latent (MLA) attention caches ONE row a token a layer, ``[c_kv |
k_rope | 0]``, that is key and value at once and has no head axis
(``ops/pallas/mla_attention.py``). With ``latent=True`` the pool is built
for ``num_kv_heads`` = 1 and ``head_dim`` = the row's width (whole 128-lane
registers, so that the pool rests in the layout its readers take):
``pages_k`` is ``(L, N, 1, bs, row)`` from the same allocator and tables,
and the value pool is NOT allocated: ``pages_v`` is a stub of one register
that rides through the step programs untouched.

State slots
-----------
A model some of whose layers keep a STATE that every position updates in
place (Gated DeltaNet: ``nn.attention.GatedDeltaNet``) holds, beside the
pages of its other layers, a group of fixed-size SLOTS (``StateSlots``,
``pool.slots``): a slot is one row's state in every such layer, the
convolution's last positions and the recurrent state. A request takes a slot
at admission and gives it back when it leaves; admission counts it beside the
pages. A row holds THREE sets of a slot: ``live``, which every step advances,
and two snapshots. A step that takes a row from a position that is a multiple
of ``nn.attention.SNAPSHOT_EVERY`` writes the state it READ into snapshot
``(position / SNAPSHOT_EVERY) % 2`` (``nn.attention.snapshot_slots``): while
the overlapped loop runs up to ``engine.SPECULATE_MAX`` <= ``SNAPSHOT_EVERY``
steps ahead of the commit, the
snapshot at or before the committed position is never overwritten, so a
rolled-back chain restores it (``restore``) and pushes the few committed
tokens behind it again. A step's packed table carries the row's slot as its
LAST entry (``table_width``); slot 0 is scratch, as block 0 is.
"""
from __future__ import annotations

import math
import os
from collections import Counter, OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_attention import (QuantPages, group_segments,
                                           lane_pack, window_table_pages)


class PoolExhausted(RuntimeError):
    """No free blocks — the scheduler preempts and retries."""


class StateSlots:
    """The pool's state group (module docstring: State slots): ``rows``
    slots (+ the scratch slot 0) of ``layers`` layers' state each, in four
    device arrays that ride through the step programs donated, like the
    pages: ``conv`` (L, rows + 1, taps - 1, channels) and ``rec`` (L, rows +
    1, heads, Dk, Dv) float32, the live states; ``conv_snap`` / ``rec_snap``
    with ``2 * rows + 1`` slots, a row's two snapshots at ``2 * (slot - 1) +
    1`` and ``+ 2`` (0: the dump slot of a row that keeps nothing)."""

    def __init__(self, group: dict, rows: int, dtype, sharding=None):
        self.layers, self.rows = int(group["layers"]), int(rows)
        self.conv_shape = tuple(group["conv"])
        self.rec_shape = tuple(group["rec"])
        self.dtype, self.sharding = dtype, sharding
        self._free: List[int] = list(range(self.rows, 0, -1))
        # made by ``reset`` (the pool's ``reset_pages`` calls it), with
        # ``nbytes``: the four arrays' bytes, live states and snapshots
        self.arrays: Dict[str, jax.Array] = {}
        self.nbytes = 0

    def reset(self) -> None:
        """Fresh zeroed arrays (explicit puts: ``PagedKVPool.reset_pages``
        says why)."""
        def zeros(slots, shape, dtype):
            x = np.zeros((self.layers, slots) + shape, np.dtype(dtype))
            return jax.device_put(x, self.sharding) if self.sharding \
                is not None else jax.device_put(x)

        live, kept = self.rows + 1, 2 * self.rows + 1
        self.arrays = {
            "conv": zeros(live, self.conv_shape, self.dtype),
            "rec": zeros(live, self.rec_shape, np.float32),
            "conv_snap": zeros(kept, self.conv_shape, self.dtype),
            "rec_snap": zeros(kept, self.rec_shape, np.float32)}
        self.nbytes = sum(int(x.nbytes) for x in self.arrays.values())

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return (self.rows - len(self._free)) / max(self.rows, 1)

    def alloc(self) -> int:
        if not self._free:
            raise PoolExhausted("no free state slot")
        return self._free.pop()

    def free(self, slot: int) -> None:
        if not 1 <= slot <= self.rows or slot in self._free:
            raise ValueError(f"state slot {slot} is not held")
        self._free.append(slot)

    def deleted(self) -> bool:
        return getattr(self.arrays["rec"], "is_deleted", lambda: False)()

    def check_invariants(self, held: Sequence[int]) -> None:
        """Every slot is free or held by exactly one running request."""
        held = list(held)
        if sorted(held + self._free) != list(range(1, self.rows + 1)):
            raise ValueError(f"state slots: held {sorted(held)} and free "
                             f"{sorted(self._free)} are not a partition of "
                             f"1..{self.rows}")


@jax.named_scope("state_restore")
def restore_state(arrays, slots, snaps):
    """``live[slots[i]] <- snapshot[snaps[i]]`` in every layer, a row at a
    time (the others: the scratch slot from the dump slot): what a
    rolled-back chain's rows go back to. Traces into the engine's
    ``tnn_state_restore``; with the arrays donated it moves the named rows
    and nothing else (no temporary wider than one row's state)."""
    def one(i, live):
        conv, rec = live
        put = jax.lax.dynamic_update_index_in_dim
        take = jax.lax.dynamic_index_in_dim
        return (put(conv, take(arrays["conv_snap"], snaps[i], 1), slots[i], 1),
                put(rec, take(arrays["rec_snap"], snaps[i], 1), slots[i], 1))

    conv, rec = jax.lax.fori_loop(0, slots.shape[0], one,
                                  (arrays["conv"], arrays["rec"]))
    return dict(arrays, conv=conv, rec=rec)


class PagedKVPool:
    SCRATCH = 0  # reserved block for padded/inactive batch rows

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int,
                 num_blocks: int, block_size: int = 16, dtype=jnp.float32,
                 kv_dtype: str = "f32", sharding=None, sp: int = 1,
                 window: Optional[int] = None, chunk: Optional[int] = None,
                 latent: bool = False, groups: Optional[dict] = None,
                 state: Optional[dict] = None, state_rows: int = 0):
        # layers that keep a state updated in place: see "State slots"
        if state and (window is not None or latent or groups or sp > 1
                      or kv_dtype != "f32" or state_rows < 1):
            raise ValueError(
                "a pool with state slots holds plain K/V pages for its "
                "other layers and is neither windowed, latent, of two "
                "groups, block-sharded (sp) nor int8")
        self.slots: Optional[StateSlots] = StateSlots(
            state, state_rows, dtype, sharding) if state else None
        if groups and (window is not None or latent or sp > 1
                       or kv_dtype != "f32" or num_layers != 1
                       or min(groups["full_layers"],
                              groups["window_layers"]) < 1):
            raise ValueError(
                "a pool of two page groups (window layers beside global "
                "ones) is ONE layer of pages, each a layer's of one group, "
                "and is neither EVA-windowed, latent, block-sharded (sp) "
                "nor int8")
        # sliding-window layers beside global layers: see "Two page groups"
        self.sliding = int(groups["window"]) if groups else None
        self.full_layers = int(groups["full_layers"]) if groups else 0
        self.window_layers = int(groups["window_layers"]) if groups else 0
        self.win_pages = window_table_pages(self.sliding, block_size) \
            if groups else 0
        if latent and (window is not None or sp > 1 or kv_dtype != "f32"
                       or num_kv_heads != 1 or head_dim % 128):
            raise ValueError(
                "a latent pool holds one row of whole 128-lane registers a "
                "token (num_kv_heads 1) and is neither windowed, "
                "block-sharded (sp) nor int8")
        self.latent = bool(latent)
        if (window is None) != (chunk is None):
            raise ValueError("window and chunk come together")
        if window is not None:
            if window % block_size or window % chunk or chunk < 1:
                raise ValueError(
                    f"window {window} must be a multiple of block_size "
                    f"{block_size} and of chunk {chunk}")
            if sp > 1 or kv_dtype != "f32":
                raise ValueError(
                    "a windowed pool (exact pages beside summary pages) is "
                    "neither block-sharded (sp) nor int8")
        self.window = window
        self.chunk = chunk
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved scratch)")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(f"kv_dtype must be 'f32' or 'int8', "
                             f"got {kv_dtype!r}")
        if sp < 1:
            raise ValueError(f"sp must be >= 1, got {sp}")
        if num_blocks % sp:
            raise ValueError(f"num_blocks {num_blocks} must divide evenly "
                             f"over sp {sp} shards")
        if sp > 1 and num_blocks // sp < 2:
            raise ValueError(f"num_blocks {num_blocks} leaves < 2 blocks "
                             f"per shard at sp {sp} (each shard reserves "
                             "one scratch block)")
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype
        self.kv_dtype = kv_dtype
        # sequence-parallel serving: the block axis is range-partitioned
        # over ``sp`` shards — shard s owns GLOBAL block ids
        # [s * N_local, (s+1) * N_local) with N_local = num_blocks // sp,
        # and each shard's local row 0 (global id s * N_local) is reserved
        # as that shard's scratch page. Host bookkeeping stays GLOBAL and
        # replicated; only alloc placement (``alloc(..., start=)`` steers a
        # table position's block to its round-robin owner shard) and the
        # per-shard capacity accounting below are sp-aware.
        self.sp = int(sp)
        self.blocks_per_shard = self.num_blocks // self.sp
        self._scratch = frozenset(s * self.blocks_per_shard
                                  for s in range(self.sp))
        # tensor-parallel serving: a NamedSharding splitting the head axis
        # over the TP mesh (serving/tp.PAGE_SPEC) — or, under SP, the block
        # axis over the context mesh (serving/sp.PAGE_SPEC). Bookkeeping
        # (free list, refcounts, tables) never looks inside a bundle, so
        # only page creation here and in reset_pages cares; one sharding
        # covers both QuantPages leaves.
        self.sharding = sharding
        # heads side by side in a page row (``lane_pack``'s rule), from the
        # heads ONE device holds: a tensor-parallel shard then owns whole
        # groups. A windowed pool stays unpacked: its readers
        # (``tnn_eva_attention``, ``write_summaries``) take rows of ``Dh``.
        flat = (self.num_layers, self.num_blocks, self.num_kv_heads,
                self.block_size, self.head_dim)
        local = sharding.shard_shape(flat)[2] if sharding is not None \
            else self.num_kv_heads
        self.lane_pack = 1 if window else lane_pack(
            local, self.head_dim,
            jnp.int8 if kv_dtype == "int8" else self.dtype)
        # THE shape of ``pages_k`` / ``pages_v`` (an int8 pool's ``data``;
        # its ``scale`` has the last axis 1): (L, N, H_kv / p, bs, p * Dh)
        self.page_shape = flat[:2] + (
            self.num_kv_heads // self.lane_pack, self.block_size,
            self.lane_pack * self.head_dim)
        self.reset_pages()
        # LIFO free list: freshly freed blocks are reused first (their pages
        # are warmest); scratch blocks never enter it
        self._free: List[int] = [b for b in range(self.num_blocks - 1, -1, -1)
                                 if b not in self._scratch]
        self._ref: Dict[int, int] = {}
        # evictable LRU (insertion order = eviction order, oldest first):
        # blocks whose refcount dropped to zero but whose KV content is still
        # indexed by the prefix cache. They hold no reference, count as
        # reclaimable capacity, and alloc() recycles them on demand — so
        # caching never shrinks the pool, it only delays page reuse.
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        # prefix-cache hooks (both None when caching is off): free() parks a
        # zero-ref block in the evictable LRU iff evictable_filter(block) is
        # True; reclaim_hook(blocks) is told when evictable blocks are
        # recycled so the cache can drop their index entries.
        self.evictable_filter: Optional[Callable[[int], bool]] = None
        self.reclaim_hook: Optional[Callable[[List[int]], None]] = None
        # host-tier hook: fires on the blocks a reclaim is about to recycle,
        # BEFORE reclaim_hook unindexes them — the engine fetches their page
        # content to the host KV tier while the prefix cache can still name
        # each block's chain key. Never fires from purge_evictable (page
        # content is untrustworthy there, e.g. after reset_pages).
        self.demote_hook: Optional[Callable[[List[int]], None]] = None
        # chaos hook: when set (serving.faults.FaultPlan), alloc() consults
        # it and may raise an injected PoolExhausted before mutating state
        self.fault_plan = None
        # TNN_POOL_DEBUG=1: re-verify bookkeeping invariants on every free
        # (eviction) — cheap O(blocks) host work, off by default
        self.debug = os.environ.get("TNN_POOL_DEBUG", "") == "1"

    # -- bookkeeping ----------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable blocks (total minus the reserved scratch block —
        one per sequence-parallel shard, so ``num_blocks - sp``)."""
        return self.num_blocks - self.sp

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_evictable(self) -> int:
        """Zero-ref blocks parked for the prefix cache (reclaimable)."""
        return len(self._evictable)

    @property
    def num_allocatable(self) -> int:
        """Blocks an alloc() can take right now: free + evictable.

        Under sequence parallelism a table position's block must come from
        its round-robin owner shard, so the BOTTLENECK shard gates
        admission: the aggregate is ``sp * min_s(free_s + evictable_s)``
        — exactly the largest contiguous run of table positions that is
        guaranteed allocatable from any starting position. The scheduler
        consults only this property, so bottleneck gating falls out with
        no scheduler change."""
        if self.sp == 1:
            return len(self._free) + len(self._evictable)
        return self.sp * min(self._shard_avail(s) for s in range(self.sp))

    @property
    def num_allocated(self) -> int:
        return self.capacity - len(self._free) - len(self._evictable)

    @property
    def occupancy(self) -> float:
        """Fraction of capacity held by live requests (evictable blocks are
        reclaimable, so they count as available, not occupied)."""
        return self.num_allocated / max(self.capacity, 1)

    @property
    def page_itemsize(self) -> int:
        """Bytes per stored KV element in the page arrays (1 under int8)."""
        if self.kv_dtype == "int8":
            return 1
        return int(np.dtype(self.dtype).itemsize)

    @property
    def kv_bytes_per_token(self) -> int:
        """Page-array bytes one resident token costs (K + V, all layers).

        Counts the page data only — the int8 scale sidecar is reported
        separately (``kv_scale_bytes_per_token``) because it is the part
        that does NOT shrink with the page dtype. A latent pool has no V."""
        return (1 if self.latent else 2) * self.num_layers \
            * self.num_kv_heads * self.head_dim * self.page_itemsize

    @property
    def kv_scale_bytes_per_token(self) -> int:
        """Sidecar bytes per token: one f32 scale per (position, head) for
        K and V each under int8; zero otherwise."""
        if self.kv_dtype != "int8":
            return 0
        return 2 * self.num_layers * self.num_kv_heads * 4

    def pages_deleted(self) -> bool:
        """True when the page buffers were donated into a step that died
        (the arrays are deleted, so the next step would crash). Looks at
        the data leaf under int8 — the bundle's leaves live and die
        together because they are donated together."""
        leaf = self.pages_k.data if isinstance(self.pages_k, QuantPages) \
            else self.pages_k
        return getattr(leaf, "is_deleted", lambda: False)() or (
            self.slots is not None and self.slots.deleted())

    @property
    def cache(self):
        """Every device array of the pool as ONE value, what a step program
        takes donated and returns: ``(pages_k, pages_v)``, and of a pool
        with state slots the state group's arrays as a third leaf (a latent
        pool's ``pages_v`` is its stub)."""
        pages = (self.pages_k, self.pages_v)
        return pages if self.slots is None else pages + (self.slots.arrays,)

    @cache.setter
    def cache(self, cache) -> None:
        """Adopt what a jitted program returned for the cache it was given
        (the donated one is dead by then)."""
        self.pages_k, self.pages_v, *state = cache
        if state:
            self.slots.arrays, = state

    @property
    def state(self):
        """The state group's device arrays (None: pages only): the third
        leaf of ``cache``."""
        return self.slots.arrays if self.slots is not None else None

    @state.setter
    def state(self, arrays) -> None:
        self.slots.arrays = arrays

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` cache positions (in a pool
        of two page groups: PAGES, each a block a layer of its group)."""
        return max(1, math.ceil(num_tokens / self.block_size))

    # -- two kinds of page: what a request needs of each ------------------

    @property
    def exact_width(self) -> int:
        """Entries of a packed step table that are exact pages (0: all)."""
        return self.window // self.block_size if self.window else 0

    def table_need(self, cache_len: int, new_tokens: int = 0):
        """(exact, summary) table lengths a request needs to hold
        ``cache_len`` resident positions and write ``new_tokens`` more (which
        lie in one window: ``room_in_window``)."""
        if self.window is None:
            return (max(self.full_layers, 1)
                    * self.blocks_for(cache_len + new_tokens), 0)
        end = cache_len + new_tokens
        in_window = end - (cache_len // self.window) * self.window
        return (math.ceil(in_window / self.block_size),
                math.ceil((end // self.chunk) / self.block_size))

    def room_in_window(self, cache_len: int) -> int:
        """Tokens a step may write from ``cache_len`` before the window
        ends (a step never crosses a window's end: the pages of the old
        window are released between two steps); in a pool of two page
        groups, before the window table is full (pages are released between
        two steps there too: at least ``bs + 2``)."""
        if self.sliding:
            return (self.release_behind(cache_len) + self.win_pages) \
                * self.block_size - cache_len
        if self.window is None:
            return 1 << 62
        return self.window - cache_len % self.window

    # -- two page groups: the window group's pages come and go ------------

    def release_behind(self, cache_len: int) -> int:
        """The first logical page a row of ``cache_len`` resident positions
        still needs in its window layers: the next query, at ``cache_len``,
        sees back to ``cache_len - window + 1``; pages wholly before that
        are no step's to read again."""
        return max(0, cache_len - self.sliding + 1) // self.block_size

    def window_need(self, cache_len: int, new_tokens: int, base: int) -> int:
        """Entries of a window table whose entry 0 is logical page ``base``
        that hold ``cache_len`` positions and ``new_tokens`` more."""
        if not self.sliding or cache_len + new_tokens <= 0:
            return 0
        last = (cache_len + new_tokens - 1) // self.block_size
        return self.window_layers * (last - base + 1)

    @property
    def kinds(self):
        """How ``step_build._fill_row`` lays a request's two tables into a
        packed row: (global layers, window layers, entries a window layer),
        ``paged_attention.group_segments``' arguments after the row's width.
        None: one group."""
        if not self.sliding:
            return None
        return (self.full_layers, self.window_layers, self.win_pages)

    def lifetime_blocks(self, total_tokens: int) -> int:
        """The most blocks a request of ``total_tokens`` positions (prompt
        and output) ever holds: will it fit to its last token."""
        if self.sliding:
            pages = self.blocks_for(total_tokens)
            return (self.full_layers * pages
                    + self.window_layers * min(pages, self.win_pages))
        if self.window is None:
            return self.blocks_for(total_tokens)
        return (math.ceil(min(total_tokens, self.window) / self.block_size)
                + math.ceil((total_tokens // self.chunk) / self.block_size))

    def admission_blocks(self, first_tokens: int, total_tokens: int) -> int:
        """Blocks an admission is planned against: what the request's first
        step needs, or, in a windowed pool (whose pages come and go, so that
        "fits now" says nothing), what it needs to its last token."""
        if self.window is None and not self.sliding:
            return self.blocks_for(first_tokens)
        return self.lifetime_blocks(total_tokens)

    def table_width(self, total_tokens: int) -> int:
        """Entries of the packed step table a request of ``total_tokens``
        positions needs: the exact segment whole, then its summaries."""
        if self.sliding:    # a segment a layer, then the window's base
            return (self.full_layers * self.blocks_for(total_tokens)
                    + self.window_layers * self.win_pages + 1)
        if self.window is None:
            # a pool with state slots: the row's slot rides as the last entry
            return self.blocks_for(total_tokens) + (self.slots is not None)
        return self.exact_width + max(1, math.ceil(
            (total_tokens // self.chunk) / self.block_size))

    @property
    def token_capacity(self) -> int:
        """The longest request the pool could hold alone."""
        if self.sliding:
            rest = self.capacity - self.window_layers * self.win_pages
            return max(0, rest) // self.full_layers * self.block_size
        if self.window is None:
            return self.capacity * self.block_size
        rest = self.capacity - self.exact_width
        return max(0, rest) * self.block_size * self.chunk

    def owner(self, block: int) -> int:
        """Sequence-parallel shard a global block id lives on."""
        return block // self.blocks_per_shard

    def _shard_avail(self, shard: int) -> int:
        """Free + evictable blocks owned by one SP shard."""
        return (sum(1 for b in self._free if self.owner(b) == shard)
                + sum(1 for b in self._evictable if self.owner(b) == shard))

    def _shard_need(self, n: int, start: int) -> List[int]:
        """Per-shard block demand of ``n`` table positions from ``start``
        (position j's block lives on shard ``j % sp``)."""
        need = [0] * self.sp
        for i in range(n):
            need[(start + i) % self.sp] += 1
        return need

    def can_alloc(self, n: int, start: int = 0) -> bool:
        if self.sp == 1:
            return n <= len(self._free) + len(self._evictable)
        return all(need <= self._shard_avail(s)
                   for s, need in enumerate(self._shard_need(n, start)))

    def is_evictable(self, block: int) -> bool:
        return block in self._evictable

    def _pick_free(self, shard: int) -> Optional[int]:
        """Pop the most-recently-freed block owned by ``shard`` (keeps the
        LIFO warm-reuse property per shard)."""
        for i in range(len(self._free) - 1, -1, -1):
            if self.owner(self._free[i]) == shard:
                return self._free.pop(i)  # tnnlint: disable=unpaired-pool-mutation -- the popped block is set-less only until alloc() re-homes it into _ref; alloc runs _debug_check() after its shard loop, and a mid-pick check would false-trip the strict partition
        return None

    def _reclaim_shard(self, shard: int) -> bool:
        """Reclaim the LRU-oldest evictable block owned by ``shard`` into
        the free list (same demote/reclaim hook contract as _reclaim)."""
        for b in self._evictable:
            if self.owner(b) == shard:
                del self._evictable[b]
                self._free.append(b)
                if self.demote_hook is not None:
                    self.demote_hook([b])
                if self.reclaim_hook is not None:
                    self.reclaim_hook([b])
                self._debug_check()
                return True
        return False

    def alloc(self, n: int, start: int = 0) -> List[int]:
        """Take ``n`` blocks (refcount 1 each); raises PoolExhausted.

        Under pressure the free list is topped up by reclaiming LRU-oldest
        evictable blocks first (``reclaim_hook`` is told so the prefix cache
        drops their index entries) — cached pages are recycled before any
        allocation can fail.

        Under sequence parallelism, ``start`` is the table POSITION the
        first returned block will occupy: block i is drawn from the free
        list of shard ``(start + i) % sp``, so a sequence's pages spread
        round-robin over the context mesh and each shard's attention sweep
        covers ~1/sp of the sequence. At sp=1 ``start`` is ignored and the
        behavior is byte-identical to the classic single-list pool."""
        if self.sp == 1:
            if n > len(self._free) + len(self._evictable):
                raise PoolExhausted(
                    f"need {n} blocks, {len(self._free)} free + "
                    f"{len(self._evictable)} evictable "
                    f"(capacity {self.capacity})")
            if self.fault_plan is not None:
                # may raise an injected PoolExhausted; fires BEFORE any state
                # mutation so a rejected alloc never half-takes blocks (nor
                # evicts cache entries for an allocation that never happens)
                self.fault_plan.on_alloc(n, self.num_allocatable)
            if n > len(self._free):
                self._reclaim(n - len(self._free))
            blocks = [self._free.pop() for _ in range(n)]
            for b in blocks:
                self._ref[b] = 1
            self._debug_check()
            return blocks
        need = self._shard_need(n, start)
        short = [(s, nd, self._shard_avail(s))
                 for s, nd in enumerate(need) if nd > self._shard_avail(s)]
        if short:
            s, nd, av = short[0]
            raise PoolExhausted(
                f"need {n} blocks from table position {start}, but shard "
                f"{s} can cover only {av} of its {nd} "
                f"(capacity {self.capacity}, {self.sp} SP shards)")
        if self.fault_plan is not None:
            self.fault_plan.on_alloc(n, self.num_allocatable)
        blocks = []
        for i in range(n):
            s = (start + i) % self.sp
            b = self._pick_free(s)
            if b is None:
                self._reclaim_shard(s)
                b = self._pick_free(s)
            self._ref[b] = 1
            blocks.append(b)
        self._debug_check()
        return blocks

    def _reclaim(self, n: int, demote: bool = True) -> List[int]:
        """Move ``n`` LRU-oldest evictable blocks to the free list and
        notify ``reclaim_hook`` (their cached KV leaves the device for
        good). With a ``demote_hook`` wired (host KV tier) and ``demote``
        true, the hook sees the blocks FIRST — while the prefix cache still
        maps block -> chain key and the pages still hold their content — so
        the engine can salvage each block to host RAM before the index
        entry dies. The hook is best-effort: whatever it does, reclaim
        proceeds identically (the tier can only add hits, never block an
        allocation)."""
        taken = []
        for _ in range(n):
            b, _ = self._evictable.popitem(last=False)
            taken.append(b)
            self._free.append(b)
        if taken and demote and self.demote_hook is not None:
            self.demote_hook(taken)
        if taken and self.reclaim_hook is not None:
            self.reclaim_hook(taken)
        self._debug_check()
        return taken

    def fork(self, blocks: Sequence[int]) -> List[int]:
        """Share ``blocks`` with another sequence (copy-on-write prefix
        reuse): bump each refcount; the caller stores the same ids.
        An EVICTABLE block is revived — a prefix-cache hit on a block no
        live request holds pulls it back to refcount 1."""
        for b in blocks:
            if b in self._ref:
                self._ref[b] += 1
            elif b in self._evictable:
                del self._evictable[b]
                self._ref[b] = 1
            else:
                raise KeyError(f"block {b} is not allocated")
        self._debug_check()
        return list(blocks)

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; blocks reaching zero return to the
        free list — unless the prefix cache still indexes their content
        (``evictable_filter``), in which case they park in the evictable
        LRU. Blocks are processed deepest-first so a released table's chain
        TAIL sits nearer the LRU's reclaim end than its parents (reclaiming
        a parent first would orphan the children's index entries)."""
        for b in reversed(list(blocks)):
            r = self._ref.get(b)
            if r is None:
                raise KeyError(f"block {b} is not allocated (double free?)")
            if r == 1:
                del self._ref[b]
                if (self.evictable_filter is not None
                        and self.evictable_filter(b)):
                    self._evictable[b] = None    # newest = last reclaimed
                else:
                    self._free.append(b)
            else:
                self._ref[b] = r - 1
        self._debug_check()

    def _debug_check(self) -> None:
        """Partition self-check after every bookkeeping mutation, active
        under TNN_POOL_DEBUG=1 — so a broken free/allocated/evictable
        partition raises at the mutation that broke it, not at decode."""
        if self.debug:
            self.check_invariants()

    def truncate(self, block_table: Sequence[int],
                 num_tokens: int) -> List[int]:
        """Shrink one sequence's table to exactly the blocks covering its
        first ``num_tokens`` cache positions, freeing the tail.

        This is the speculative-decoding rollback primitive: a decode row
        grows blocks for ``1 + k`` candidate positions up front, and when the
        verifier rejects a draft suffix the row keeps only its verified
        length. The freed tail goes through ``free()``, so the
        free/allocated/evictable partition (and prefix-cache parking) is
        preserved; tail blocks of a decode row are always refcount-1 and
        unpublished, but shared blocks would be handled correctly too — a
        fork survivor just drops one reference. Returns the kept prefix as a
        new list (the caller replaces its table with it).
        """
        keep = self.blocks_for(num_tokens) if num_tokens > 0 else 0
        if keep >= len(block_table):
            return list(block_table)
        self.free(block_table[keep:])
        return list(block_table[:keep])

    def purge_evictable(self) -> List[int]:
        """Reclaim EVERY evictable block (cache invalidation: page content
        became untrustworthy, e.g. after ``reset_pages``). Demotion is
        suppressed — salvaging zeroed or poisoned pages into the host tier
        under still-valid chain keys would turn a clean crash recovery into
        a wrong-KV re-admission later."""
        return self._reclaim(len(self._evictable), demote=False)

    def check_invariants(
            self,
            block_tables: Optional[Iterable[Sequence[int]]] = None,
            seq_lens: Optional[Sequence[int]] = None,
            summary_tables: Optional[Iterable[Sequence[int]]] = None,
            window_tables: Optional[Iterable[Sequence[int]]] = None,
            window_bases: Optional[Sequence[int]] = None) -> None:
        """Verify the pool's bookkeeping; raises ValueError on violation.

        Always checked: free + allocated + evictable == capacity (a strict
        three-way partition — no block in two sets, each evictable block in
        the LRU exactly once with refcount 0, i.e. absent from ``_ref``),
        every refcount >= 1, the scratch block never in circulation, no
        duplicate free-list entries, all ids in range. Reclaim moves blocks
        evictable -> free, so the partition is preserved by construction and
        re-verified here after every mutation in debug mode.

        With ``block_tables`` (the live tables of every running request),
        additionally checks full accounting: each allocated block appears in
        exactly ``refcount`` live tables — no leaked blocks (allocated but
        unreferenced) and no block shared beyond its refcount — and no live
        table references an evictable or free block (use-after-free).

        With ``seq_lens`` (parallel to ``block_tables``: each row's resident
        token count), additionally checks the truncate-path contract per row:
        the table covers every resident position (a rollback that cut too
        deep leaves tokens with no backing block), and carries no stale tail
        — at most ``blocks_for(seq_len + 1)`` blocks, i.e. nothing beyond
        what the pending next single-token write may legitimately pre-own
        (a full-cover prefix hit re-derives its last token copy-on-write and
        briefly holds that one extra block). A rejected draft suffix whose
        blocks were never truncated shows up here as a longer tail.

        With ``summary_tables`` (parallel too: a windowed pool's second kind
        of page) both of a row's tables are held to ``table_need``: the exact
        table covers the current window's resident positions and no more
        than the next token's, the summary table one row a finished chunk;
        and the full accounting counts the blocks of both.

        With ``window_tables`` and ``window_bases`` (parallel too: a pool of
        two page groups) a row's global table holds ``full_layers`` blocks a
        page of its context, its window table ``window_layers`` a page from
        ``window_base``, which is where ``release_behind`` puts it: a page
        behind the window that was not given back shows up here.
        """
        if self.kv_dtype == "int8":
            # scale/page agreement: both sides must still be the bundled
            # pytree with the sidecar shaped to the pages — a step that
            # re-adopted data without scales (or swapped shapes) fails
            # HERE, not as silent garbage at the next dequant
            for name, p in (("pages_k", self.pages_k),
                            ("pages_v", self.pages_v)):
                if not isinstance(p, QuantPages):
                    raise ValueError(
                        f"{name}: int8 pool holds {type(p).__name__}, not "
                        "QuantPages — a step re-adopted pages without their "
                        "scale sidecar")
                if p.data.dtype != jnp.int8 or p.scale.dtype != jnp.float32:
                    raise ValueError(
                        f"{name}: dtype drift — data {p.data.dtype} / "
                        f"scale {p.scale.dtype}, want int8 / float32")
                if p.scale.shape != p.data.shape[:-1] + (1,):
                    raise ValueError(
                        f"{name}: scale {p.scale.shape} does not match "
                        f"pages {p.data.shape} (want last axis collapsed "
                        "to 1)")
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise ValueError(f"duplicate blocks in free list: {self._free}")
        evict_set = set(self._evictable)
        leaked_scratch = self._scratch & (free_set | self._ref.keys()
                                          | evict_set)
        if leaked_scratch:
            raise ValueError(f"scratch block "
                             f"{min(leaked_scratch)} entered circulation")
        if free_set & self._ref.keys():
            raise ValueError(
                f"blocks both free and allocated: {free_set & self._ref.keys()}")
        if evict_set & self._ref.keys():
            raise ValueError(
                f"blocks both evictable and allocated (refcount != 0): "
                f"{evict_set & self._ref.keys()}")
        if evict_set & free_set:
            raise ValueError(
                f"blocks both evictable and free: {evict_set & free_set}")
        if len(self._free) + len(self._ref) + len(evict_set) != self.capacity:
            raise ValueError(
                f"free ({len(self._free)}) + allocated ({len(self._ref)}) + "
                f"evictable ({len(evict_set)}) != capacity ({self.capacity})")
        bad = [b for b in (free_set | self._ref.keys() | evict_set)
               if not 0 <= b < self.num_blocks or b in self._scratch]
        if bad:
            raise ValueError(f"block ids out of range: {bad}")
        if any(r < 1 for r in self._ref.values()):
            raise ValueError(f"refcount < 1: {self._ref}")
        if block_tables is not None:
            block_tables = [list(t) for t in block_tables]
            sums = [list(t) for t in summary_tables] \
                if summary_tables is not None else [[]] * len(block_tables)
            wins = [list(t) for t in window_tables] \
                if window_tables is not None else [[]] * len(block_tables)
            bases = list(window_bases) if window_bases is not None \
                else [0] * len(block_tables)
            if not len(sums) == len(wins) == len(bases) == len(block_tables):
                raise ValueError("summary_tables, window_tables and "
                                 "window_bases are parallel to block_tables")
            if seq_lens is not None:
                if len(list(seq_lens)) != len(block_tables):
                    raise ValueError(
                        f"seq_lens ({len(list(seq_lens))}) not parallel to "
                        f"block_tables ({len(block_tables)})")
                for i, (table, n) in enumerate(zip(block_tables, seq_lens)):
                    if self.sliding:
                        lo, hi = (self.table_need(n)[0] if n else 0,
                                  self.table_need(n, 1)[0])
                        wlo, whi = (self.window_need(n, 0, bases[i]),
                                    self.window_need(n, 1, bases[i]))
                        if not (lo <= len(table) <= hi
                                and wlo <= len(wins[i]) <= whi
                                and bases[i] == self.release_behind(n)):
                            raise ValueError(
                                f"row {i}: {n} resident tokens hold "
                                f"{len(table)} global and {len(wins[i])} "
                                f"window blocks from page {bases[i]}, want "
                                f"{lo}..{hi} and {wlo}..{whi} from page "
                                f"{self.release_behind(n)}")
                        continue
                    if self.window is not None:
                        (lo_e, lo_s), (hi_e, hi_s) = \
                            self.table_need(n), self.table_need(n, 1)
                        if not (lo_e <= len(table) <= hi_e
                                and lo_s <= len(sums[i]) <= hi_s):
                            raise ValueError(
                                f"row {i}: {n} resident tokens hold "
                                f"{len(table)} exact and {len(sums[i])} "
                                f"summary blocks, want {lo_e}..{hi_e} and "
                                f"{lo_s}..{hi_s}")
                        continue
                    if n > len(table) * self.block_size:
                        raise ValueError(
                            f"row {i}: {n} resident tokens exceed table "
                            f"coverage ({len(table)} blocks x "
                            f"{self.block_size}) — truncated too deep")
                    if len(table) > self.blocks_for(n + 1):
                        raise ValueError(
                            f"row {i}: stale tail — {len(table)} blocks for "
                            f"{n} resident tokens (max "
                            f"{self.blocks_for(n + 1)}); a rejected draft "
                            f"suffix was not truncated")
            usage: Counter = Counter()
            for table in block_tables + sums + wins:
                usage.update(table)
            for sc in self._scratch:        # padded entries are legal
                usage.pop(sc, None)
            stale = set(usage) & (evict_set | free_set)
            if stale:
                raise ValueError(
                    f"live tables reference non-allocated blocks "
                    f"(use-after-free): {sorted(stale)}")
            if set(usage) != set(self._ref) or any(
                    usage[b] != r for b, r in self._ref.items()):
                leaked = set(self._ref) - set(usage)
                unknown = set(usage) - set(self._ref)
                counts = {b: (usage[b], self._ref.get(b)) for b in usage}
                raise ValueError(
                    f"table/refcount mismatch: leaked={sorted(leaked)} "
                    f"unallocated-in-tables={sorted(unknown)} "
                    f"(table_uses, refcount)={counts}")

    def check_step_writes(self, tables, starts, q_lens) -> None:
        """Verify the ONE-WRITER invariant for one packed step; raises
        ValueError on violation.

        ``tables`` (B, nb) are the step's GLOBAL block tables, row i writes
        positions ``starts[i] .. starts[i] + q_lens[i] - 1``. The paged write
        (``ops.pallas.paged_attention.scatter_kv_rows`` / ``scatter_kv_chunk``)
        rewrites a row's tile of a page (whole pages off the chip and where
        the kernel does not take the pages), which is exact only while every non-scratch
        page a step writes belongs to one row alone: refcount 1 (a shared
        prefix page is cloned before its first write, ``_match_prefix``) and
        no second row of the batch writing it. Scratch pages take any number
        of writers — nothing reads them. The engine runs this on every
        packed step under TNN_POOL_DEBUG=1.
        """
        bs = self.block_size
        writer: Dict[int, int] = {}
        for i, (table, start, n) in enumerate(zip(tables, starts, q_lens)):
            if n <= 0:
                continue
            start, end = int(start), int(start) + int(n)
            if self.sliding:
                # every layer's segment of the packed row: a global layer's
                # at the position's page, a window layer's from the base
                ww = self.win_pages
                _, at_full, at_win = group_segments(len(table), *self.kinds)
                lo, hi, base = start // bs, (end - 1) // bs + 1, int(table[-1])
                if hi - base > ww:
                    raise ValueError(
                        f"row {i} writes page {hi - 1} of a window table "
                        f"of {ww} pages from page {base}")
                written = [blk for at in at_full
                           for blk in table[at + lo:at + hi]]
                written += [blk for at in at_win
                            for blk in table[at + lo - base:at + hi - base]]
            elif self.window is None:
                written = table[start // bs:(end - 1) // bs + 1]
            else:
                # the window's exact pages at window-relative positions,
                # then the summary page of every chunk the tokens complete
                rel, w = start % self.window, self.exact_width
                if rel + int(n) > self.window:
                    raise ValueError(
                        f"row {i} writes {start}..{end - 1} across the end "
                        f"of a window of {self.window}")
                written = list(table[rel // bs:(rel + int(n) - 1) // bs + 1])
                c0, c1 = start // self.chunk, end // self.chunk
                if c1 > c0:
                    written += list(table[w + c0 // bs:w + (c1 - 1) // bs + 1])
            for blk in written:
                blk = int(blk)
                if blk in self._scratch:
                    continue
                if blk in writer:
                    raise ValueError(
                        f"block {blk} written by rows {writer[blk]} and {i} "
                        "of one step — the page write needs one "
                        "writer per page")
                writer[blk] = i
                if self._ref.get(blk) != 1:
                    raise ValueError(
                        f"row {i} writes block {blk} with refcount "
                        f"{self._ref.get(blk)} — a shared or unowned page "
                        "must be cloned before its first write")

    # -- device pages ---------------------------------------------------------

    def update_pages(self, pages_k, pages_v) -> None:
        """Adopt the functionally-updated page arrays a jitted step returned."""
        self.pages_k = pages_k
        self.pages_v = pages_v

    def reset_pages(self) -> None:
        """Re-zero the device pages (fresh buffers). Recovery path for a
        failed jitted step whose DONATED page buffers died with it: the
        engine fails every request that held KV first, so only bookkeeping
        (untouched here) and empty pages remain. Callers running a prefix
        cache must also ``purge_evictable()`` and clear the cache index —
        zeroed pages must never be matchable. Under tensor parallelism the
        puts honor ``self.sharding``, so a crash reset purges EVERY shard's
        pages, not just the default device's."""
        shape = self.page_shape

        # explicit puts, not jnp.zeros: recovery runs inside the step's
        # TNN_DEBUG_SYNC transfer guard, where eager jnp ops (which commit
        # their scalar operands implicitly) are disallowed
        def put(x):
            if self.sharding is not None:
                return jax.device_put(x, self.sharding)
            return jax.device_put(x)

        if self.kv_dtype == "int8":
            def fresh():
                return QuantPages(
                    put(np.zeros(shape, np.int8)),
                    put(np.zeros(shape[:-1] + (1,), np.float32)))
            self.pages_k = fresh()
            self.pages_v = fresh()
        else:
            self.pages_k = put(np.zeros(shape, np.dtype(self.dtype)))
            # a latent row is key and value at once: no value pool, a stub
            self.pages_v = put(np.zeros(
                (self.num_layers, 1, 1, 8, 128) if self.latent else shape,
                np.dtype(self.dtype)))
        if self.slots is not None:
            self.slots.reset()

    def export_blocks(self, blocks: Sequence[int]) \
            -> List[tuple]:
        """Fetch whole pages to the host, one leaf tuple per block: ONE
        batched explicit ``jax.device_get`` covering every requested block
        (this runs outside the step's fetch/commit machinery — the demote
        hook and the cross-replica export path, never a step-path call).
        f32 pools yield ``(k_slice, v_slice)``; int8 pools yield
        ``(k_data, k_scale, v_data, v_scale)`` — the int8 payload ships
        both leaves at ~half the f32 wire bytes, scale sidecar included.
        The leaf order is exactly what ``write_block`` payloads (and the
        host tier's ``demote``) consume, so an exported block re-adopts
        byte-identically anywhere with the same pool geometry."""
        pk, pv = self.pages_k, self.pages_v
        fetch = []
        for b in blocks:
            if isinstance(pk, QuantPages):
                fetch.append((pk.data[:, b], pk.scale[:, b],
                              pv.data[:, b], pv.scale[:, b]))
            else:
                fetch.append((pk[:, b], pv[:, b]))
        return list(jax.device_get(tuple(fetch))) if fetch else []

    def adopt_blocks(self, items: Sequence[tuple], write_fn,
                     put: Callable) -> None:
        """Write exported payloads into already-allocated blocks — the
        device half of re-admission/handoff. ``items`` is a sequence of
        ``(block_id, payload_k, payload_v)`` where the payloads are
        device-resident values shaped for ``write_block`` (QuantPages
        bundles under int8); ``write_fn`` is the caller's compiled
        ``(cache, blk, (payload_k, payload_v)) -> ((), cache', ())`` adopt step
        (donation/compile-key discipline stays with
        the engine) and ``put`` the caller's explicit host->device
        transfer for the traced block id. Callers MUST digest-verify wire
        payloads (``kv_tier.tier_digest``) before handing them here — the
        ``tier-adopt-unverified`` lint rule enforces it at every call
        site."""
        for blk, payload_k, payload_v in items:
            _, self.cache, _ = write_fn(self.cache, put(blk, jnp.int32),
                                        (payload_k, payload_v))

    def padded_table(self, block_table: Sequence[int], width: int):
        """Right-pad a block table with SCRATCH to a fixed ``width``."""
        if len(block_table) > width:
            raise ValueError(f"block table of {len(block_table)} exceeds "
                             f"assembly width {width}")
        return list(block_table) + [self.SCRATCH] * (width - len(block_table))


# -- whole-page writes (trace into the engine's compiled COW/adopt steps) ----


@jax.named_scope("kv_write")
def write_block(pages, block, payload):
    """Write one whole page at ``block`` across every layer (the host-tier
    re-admission's device half). pages: the pool's (L, N, H / p, bs,
    p * Dh); block: scalar int32 (traced — one compiled fn serves every
    block id); payload: (L, H / p, bs, p * Dh). Under QuantPages the
    payload is itself a QuantPages of slices, so the int8 data and its f32
    scale sidecar are re-adopted together — a readmitted block can never
    dequantize against stale scales.
    """
    if isinstance(pages, QuantPages):
        return QuantPages(write_block(pages.data, block, payload.data),
                          write_block(pages.scale, block, payload.scale))
    return pages.at[:, block].set(payload)


@jax.named_scope("kv_write")
def copy_blocks(pages, src, dst):
    """Copy whole pages ``src -> dst`` across every layer (the COW split's
    device half). src/dst: (n,) int32 block ids. Under QuantPages the scale
    sidecar is copied with its pages, so a cloned block dequantizes
    identically to its source.
    """
    if isinstance(pages, QuantPages):
        return QuantPages(copy_blocks(pages.data, src, dst),
                          copy_blocks(pages.scale, src, dst))
    return pages.at[:, dst].set(pages[:, src])
