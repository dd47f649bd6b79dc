"""Attention: fused scaled-dot-product attention + multi-head attention layer.

Reference parity:
  * AttentionBlock — include/nn/blocks_impl/attention_block.hpp:21 — q/k/v/out Dense
    projections + batched QK^T -> causal mask -> softmax -> xV via cuBLAS strided-batch
    (src/nn/blocks_impl/attention_block.cpp:109-315; CPU path throws).
  * FlashAttentionBlock — cuDNN-frontend fused SDPA (src/nn/blocks_impl/flash_attention_block.cpp:74-338).
  * SDPALayer — layers_impl/sdpa_layer.hpp:23.

TPU-first: one SDPA implementation with pluggable backends — "xla" (lax ops XLA fuses
well, works everywhere) and "pallas" (blockwise online-softmax flash kernel for long
sequences, tnn_tpu/ops/pallas/flash_attention.py). Both are O(S^2) FLOPs but pallas is
O(block) memory like the reference's flash path. Unlike the reference, attention runs on
every backend (the reference throws on CPU).

KV-cache decode support (``apply_cached``) exceeds the reference, which recomputes the
full sequence per generated token (examples/gpt2_inference.cpp:71-91).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import dtypes as dt
from ..core.module import Module, register_module
from . import initializers


# Ring (sequence-parallel) context: inside ``with ring_context(mesh):``, every
# sdpa call that CAN run as a ring (no mask/kv_offset) does — regardless of the
# model's configured backend. The context is authoritative because sequence
# parallelism is a run-time deployment choice, not model configuration: the
# model object is never mutated, so checkpoints keep their original backend
# and the same model decodes single-chip after seq-parallel training.
# sdpa(backend="ring") outside any context is an error (nothing to ring over).
_RING_CTX = {"mesh": None, "axis": "seq", "batch_axis": None, "method": "ring"}


class ring_context:
    """with ring_context(mesh, axis="seq"): step(...) — seq-parallel attention.
    ``batch_axis`` (a name or tuple of names) composes dp/fsdp x sp: each batch
    shard runs its own ring instead of all-gathering at the shard_map boundary.
    ``method`` picks the context-parallel scheme: "ring" (K/V rotation — any
    head count) or "ulysses" (all-to-all head re-sharding — needs
    num_heads % sp == 0, runs the Pallas flash kernel locally)."""

    def __init__(self, mesh, axis: str = "seq", batch_axis=None,
                 method: str = "ring"):
        if method not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq-parallel method {method!r}")
        self.mesh, self.axis, self.batch_axis = mesh, axis, batch_axis
        self.method = method

    def __enter__(self):
        self._prev = dict(_RING_CTX)
        _RING_CTX.update(mesh=self.mesh, axis=self.axis,
                         batch_axis=self.batch_axis, method=self.method)
        return self

    def __exit__(self, *exc):
        _RING_CTX.update(self._prev)


def count_attention_modules(module) -> int:
    """How many submodules carry a switchable attention ``backend`` — used to
    validate that a seq-parallel layout has attention to parallelize.
    (backend=None in set_attention_backend counts without mutating.)"""
    return set_attention_backend(module, None)


def set_attention_backend(module, backend) -> int:
    """Recursively set ``backend`` on every attention-bearing submodule.

    Returns how many modules were switched. Retargets a model built with
    backend="xla" to "pallas" (etc.) without rebuilding it — the attribute is
    read at trace time, not baked at init. (Sequence parallelism does NOT need
    this: ring_context overrides backends without mutating the model.)

    The walk follows Module attributes, list/tuple elements, dict values, and
    non-Module wrappers exposing ``.module`` (Graph's GraphNode)."""
    from ..core.module import Module

    seen = set()
    count = 0

    def walk(m):
        nonlocal count
        if id(m) in seen or not isinstance(m, Module):
            return
        seen.add(id(m))
        if hasattr(m, "backend"):
            if backend is not None:
                m.backend = backend
            count += 1
        for v in vars(m).values():
            for x in _iter_candidates(v):
                walk(x)

    def _iter_candidates(v):
        if isinstance(v, Module):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from _iter_candidates(x)
        elif isinstance(v, dict):
            for x in v.values():
                yield from _iter_candidates(x)
        elif hasattr(v, "module"):  # GraphNode-style wrapper
            yield from _iter_candidates(v.module)

    walk(module)
    return count


def sdpa(q, k, v, *, causal: bool = False, mask: Optional[jax.Array] = None,
         scale: Optional[float] = None, backend: str = "xla",
         kv_offset: Optional[jax.Array] = None):
    """Scaled dot-product attention over (B, H, S, Dh) tensors.

    ``kv_offset``: during cached decode, absolute position of q[0] within the kv
    sequence — builds the correct causal mask for S_q != S_kv. May be a scalar
    (uniform batch) or a (B,) array (ragged batch — serving's continuous
    batching, where every row sits at its own decode position).
    """
    ragged = kv_offset is not None and getattr(kv_offset, "ndim", 0) > 0
    # GQA + seq parallelism: ring is GQA-aware for any group ratio; ulysses
    # validates H_kv % shards itself (ulysses_attention raises a ValueError
    # naming the ring fallback when kv heads cannot split)
    ringable = mask is None and kv_offset is None
    if _RING_CTX["mesh"] is not None and ringable:
        # context wins over the configured backend: inside a seq-parallel step
        # the activations are seq-sharded, so local/full attention would be
        # wrong or all-gather; mask/kv_offset calls (cached decode) fall
        # through to their normal path untouched
        if _RING_CTX["method"] == "ulysses":
            from ..parallel.ulysses import ulysses_attention

            with jax.named_scope("ring_attn"):
                return ulysses_attention(
                    q, k, v, _RING_CTX["mesh"], axis=_RING_CTX["axis"],
                    causal=causal, scale=scale,
                    batch_axis=_RING_CTX["batch_axis"])
        from ..parallel.ring_attention import ring_attention

        with jax.named_scope("ring_attn"):
            return ring_attention(
                q, k, v, _RING_CTX["mesh"], axis=_RING_CTX["axis"],
                causal=causal, scale=scale,
                batch_axis=_RING_CTX["batch_axis"])
    if backend == "ring":
        raise RuntimeError(
            "backend='ring' needs an enclosing nn.attention.ring_context(mesh)"
            " — e.g. train_model with mesh_axes={'seq': N}" if ringable else
            "ring attention does not support mask/kv_offset (cached decode); "
            "run decode outside the ring context with backend='xla'")
    if backend == "pallas" and not ragged:
        # the flash kernel takes a scalar kv_offset only; ragged
        # assembled-cache batches route to the XLA path (ragged decode's
        # native route is the paged path — apply_paged over
        # ops.pallas.paged_attention, no assembled cache at all)
        from ..ops.pallas.flash_attention import flash_attention

        with jax.named_scope("flash_attn"):
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   mask=mask, kv_offset=kv_offset)
    with jax.named_scope("sdpa"):
        return local_xla_attention(q, k, v, causal=causal, mask=mask,
                                   scale=scale, kv_offset=kv_offset)


def apply_rope(x, offset=0, theta: float = 10000.0):
    """Rotary position embedding over (B, H, S, Dh) — half-split (NeoX-style)
    pair rotation. ``offset`` is the absolute position of x[..., 0, :] (the
    cached-decode case); may be a traced scalar, or a (B,) array for ragged
    decode batches where every row sits at its own position. Rotation is a
    function of ABSOLUTE position, so cached decode rotates keys at insert
    time and the cache stores rotated keys."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head dim, got {d}")
    half = d // 2
    if getattr(offset, "ndim", 0):  # per-row offsets: (B, S) positions
        pos = offset[:, None] + jnp.arange(x.shape[-2])
    else:
        pos = offset + jnp.arange(x.shape[-2])
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    freqs = pos[..., None].astype(jnp.float32) * inv   # (..., S, half)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if cos.ndim == 3:  # ragged: (B, S, half) -> broadcast over the head dim
        cos, sin = cos[:, None], sin[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def local_xla_attention(q, k, v, *, causal: bool = False,
                        mask: Optional[jax.Array] = None,
                        scale: Optional[float] = None,
                        kv_offset: Optional[jax.Array] = None):
    """The plain XLA softmax-attention math — sdpa's "xla" backend, and the
    single source of truth for any caller that must bypass the seq-parallel
    context routing (e.g. ulysses' off-TPU local attention, which would
    recurse through sdpa)."""
    sq, skv = q.shape[-2], k.shape[-2]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        # grouped-query attention: materialize the shared kv heads for the
        # reference path (XLA folds the broadcast); the pallas kernel is the
        # zero-copy route (q-head grid index -> kv head in its index maps)
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    # QK^T with f32 accumulation on the MXU.
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    live = None
    if causal:
        qpos = jnp.arange(sq)[:, None]
        if kv_offset is not None:
            if getattr(kv_offset, "ndim", 0):  # per-row (B,) -> (B, 1, sq, 1)
                qpos = qpos + kv_offset[:, None, None, None]
            else:
                qpos = qpos + kv_offset
        kpos = jnp.arange(skv)[None, :]
        live = qpos >= kpos
        logits = jnp.where(live, logits, dt.neg_inf(logits.dtype))
    if mask is not None:
        live = mask if live is None else jnp.logical_and(mask, live)
        logits = jnp.where(mask, logits, dt.neg_inf(logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        # a fully-masked row attends to NOTHING (output 0) — softmax alone
        # would silently return uniform attention over the masked keys; the
        # flash kernel's online-softmax (l=0 -> 0) already behaves this way
        row_live = jnp.any(jnp.broadcast_to(live, logits.shape), axis=-1,
                           keepdims=True)
        probs = jnp.where(row_live, probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


@register_module("multihead_attention")
class MultiHeadAttention(Module):
    """Multi-head self-attention over (N, S, D).

    Parity: AttentionBlock (4 Dense projections q/k/v/out + batched SDPA,
    blocks_impl/attention_block.cpp:109-315). Fused qkv projection (one matmul instead of
    three — better MXU utilisation).
    """

    def __init__(self, num_heads: int, causal: bool = False, dropout: float = 0.0,
                 backend: str = "xla", kernel_init: str = "xavier_uniform",
                 num_kv_heads: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = None,
                 rope_theta: Optional[float] = None, use_bias: bool = True,
                 window: Optional[int] = None, chunk: Optional[int] = None,
                 scale: Optional[float] = None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_heads = int(num_heads)
        # the softmax's scale; None: head_dim^-1/2 (a model that publishes
        # its own, Granite's ``attention_multiplier``, says so here)
        self.scale = float(scale) if scale else None
        if self.scale and window:
            raise ValueError("EVA attention takes no softmax scale")
        # EVA attention (ops/pallas/eva_attention.py): exact keys of the
        # current ``window`` positions beside one learned summary (``phi``,
        # ``mu`` a head) for every ``chunk`` earlier tokens. None = every
        # position exact, the plain softmax attention.
        self.window = int(window) if window else None
        self.chunk = int(chunk) if chunk else None
        if (self.window is None) != (self.chunk is None) or (
                self.window and self.window % self.chunk):
            raise ValueError(f"window {window} and chunk {chunk} come "
                             "together, the window a multiple of the chunk")
        # grouped-query attention (beyond reference): H_kv < H shares each
        # kv head across a group of query heads, shrinking the decode KV
        # cache (the decode bandwidth floor) by H/H_kv
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        if self.num_kv_heads <= 0 or self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads {self.num_kv_heads} must be a "
                             f"positive divisor of num_heads {self.num_heads}")
        # "int8": decode KV cache stored as per-row symmetric int8 + f32
        # scale — halves cache residency/traffic (composes with GQA's H/H_kv)
        if kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype {kv_cache_dtype!r}: only "
                             "None (compute dtype) or 'int8' supported")
        self.kv_cache_dtype = kv_cache_dtype
        # rotary position embedding (Llama-family): applied to q/k after the
        # projection split; absolute-position offsets flow through cached
        # decode. None = no rotation (positions come from elsewhere, e.g. a
        # learned wpe as in GPT-2).
        self.rope_theta = float(rope_theta) if rope_theta else None
        self.use_bias = bool(use_bias)
        self.causal = bool(causal)
        self.dropout = float(dropout)
        self.backend = backend
        self.kernel_init = kernel_init
        from .layers import Dropout  # local import: layers has no dep on attention

        self._drop = Dropout(self.dropout, policy=self.policy)

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        if d % self.num_heads:
            raise ValueError(f"model dim {d} not divisible by num_heads {self.num_heads}")
        kv_d = (d // self.num_heads) * self.num_kv_heads
        init = initializers.get(self.kernel_init)
        k1, k2 = jax.random.split(rng)
        pd = self.policy.param_dtype
        params = {
            "qkv_kernel": init(k1, (d, d + 2 * kv_d), pd),
            "out_kernel": init(k2, (d, d), pd),
        }
        if self.use_bias:
            params["qkv_bias"] = jnp.zeros((d + 2 * kv_d,), pd)
            params["out_bias"] = jnp.zeros((d,), pd)
        if self.window:
            dh = d // self.num_heads
            k3, k4 = jax.random.split(k2)
            for name, key in (("phi", k3), ("mu", k4)):
                params[name] = (jnp.clip(jax.random.normal(
                    key, (self.num_kv_heads, dh)), -1.0, 1.0)
                    / math.sqrt(dh)).astype(pd)
        return params, {}

    def _split_heads(self, x, h=None):
        n, s, d = x.shape
        h = h or self.num_heads
        return x.reshape(n, s, h, d // h).transpose(0, 2, 1, 3)

    def _merge_heads(self, x):
        n, h, s, dh = x.shape
        return x.transpose(0, 2, 1, 3).reshape(n, s, h * dh)

    @jax.named_scope("attn_qkv")
    def _project_qkv(self, params, x):
        from ..ops.pallas.quant_matmul import qmatmul

        x = self.policy.cast_in(x)
        w = self.policy.cast_param(params["qkv_kernel"])
        qkv = qmatmul(x, w).astype(x.dtype)
        if self.use_bias:
            qkv = qkv + params["qkv_bias"].astype(x.dtype)
        d = x.shape[-1]
        kv_d = (d // self.num_heads) * self.num_kv_heads
        q, k, v = jnp.split(qkv, [d, d + kv_d], axis=-1)
        return (self._split_heads(q), self._split_heads(k, self.num_kv_heads),
                self._split_heads(v, self.num_kv_heads))

    @jax.named_scope("attn_out")
    def _project_out(self, params, attn, train, rng):
        from ..ops.pallas.quant_matmul import qmatmul

        y = self._merge_heads(attn)
        w = self.policy.cast_param(params["out_kernel"])
        y = qmatmul(y, w).astype(y.dtype)
        if self.use_bias:
            y = y + params["out_bias"].astype(y.dtype)
        y, _ = self._drop.apply({}, y, train=train, rng=rng)
        return self.policy.cast_out(y)

    def _apply(self, params, state, x, *, train, rng):
        if self.window and x.shape[1] > self.window:
            raise NotImplementedError(
                f"a sequence of {x.shape[1]} positions is longer than the "
                f"window of {self.window}: EVA attention past one window "
                "runs against the paged pool (apply_paged)")
        q, k, v = self._project_qkv(params, x)
        if self.rope_theta:
            with jax.named_scope("attn_qkv"):
                q = apply_rope(q, 0, self.rope_theta)
                k = apply_rope(k, 0, self.rope_theta)
        attn = sdpa(q, k, v, causal=self.causal, scale=self.scale,
                    backend=self.backend)
        return self._project_out(params, attn, train, rng), state

    # -- cached autoregressive decode (exceeds reference) ----------------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        """Allocate a (k, v) ring cache for decode — sized to the KV heads,
        so GQA shrinks the cache (and the decode HBM floor) by H/H_kv;
        ``kv_cache_dtype="int8"`` halves it again (int8 rows + f32 scales)."""
        h = self.num_kv_heads
        dh = d_model // self.num_heads
        if self.kv_cache_dtype == "int8":
            z8 = jnp.zeros((batch, h, max_len, dh), jnp.int8)
            zs = jnp.zeros((batch, h, max_len, 1), jnp.float32)
            return {"k": z8, "v": z8, "k_scale": zs, "v_scale": zs}
        dtype = self.policy.compute_dtype
        return {
            "k": jnp.zeros((batch, h, max_len, dh), dtype),
            "v": jnp.zeros((batch, h, max_len, dh), dtype),
        }

    @staticmethod
    def _quant_rows(x):
        """Symmetric per-row (per position, per head) int8: scale = amax/127."""
        xf = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                            1e-8) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        return q, scale

    def apply_cached(self, variables, x, cache, offset):
        """Decode step: x is (N, S_new, D); cache holds keys/values for [0, offset).

        Returns (out, new_cache). The full cache buffer participates in attention with a
        position mask, keeping shapes static for jit.

        ``offset`` may be a scalar (uniform batch) or a (N,) array — the
        ragged case, where each row writes and masks at its own position
        (serving's continuous batching over pool-assembled caches).
        """
        params = variables["params"]
        q, k_new, v_new = self._project_qkv(params, x)
        if self.rope_theta:
            # rotation depends on ABSOLUTE position: rotate q and the new
            # keys at their true offsets; the cache stores rotated keys
            with jax.named_scope("attn_qkv"):
                q = apply_rope(q, offset, self.rope_theta)
                k_new = apply_rope(k_new, offset, self.rope_theta)
        if getattr(offset, "ndim", 0):  # per-row write positions
            upd = lambda buf, new: jax.vmap(  # noqa: E731
                lambda b, n, o: jax.lax.dynamic_update_slice_in_dim(
                    b, n, o, axis=1))(buf, new, offset)
        else:
            upd = lambda buf, new: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                buf, new, offset, axis=2)
        if self.kv_cache_dtype == "int8":
            with jax.named_scope("kv_write"):
                kq, ks = self._quant_rows(k_new)
                vq, vs = self._quant_rows(v_new)
                cache = {"k": upd(cache["k"], kq), "v": upd(cache["v"], vq),
                         "k_scale": upd(cache["k_scale"], ks),
                         "v_scale": upd(cache["v_scale"], vs)}
            cd = self.policy.compute_dtype
            # dequant at use. On the XLA backend the int8 read + scale can
            # fuse into the attention contraction (traffic = int8 bytes); on
            # backend="pallas" the dequantized arrays are pallas_call
            # operands — a fusion boundary — so THIS contiguous-cache path
            # materializes compute-dtype K/V and only the residency win
            # remains. The paged serving path does not share the caveat:
            # the pool's kv_dtype="int8" QuantPages feed the ragged paged
            # kernel as int8 operands and dequantize in-VMEM inside its
            # online-softmax loop, so HBM traffic is int8 bytes there too.
            k = (cache["k"].astype(jnp.float32) * cache["k_scale"]).astype(cd)
            v = (cache["v"].astype(jnp.float32) * cache["v_scale"]).astype(cd)
        else:
            with jax.named_scope("kv_write"):
                cache = {"k": upd(cache["k"], k_new),
                         "v": upd(cache["v"], v_new)}
            k, v = cache["k"], cache["v"]
        # decode follows the model's configured backend — a "pallas" model
        # runs the flash kernel with kv_offset instead of falling back to XLA
        out = sdpa(q, k, v, causal=True, kv_offset=offset, scale=self.scale,
                   backend=self.backend if self.backend != "ring" else "xla")
        y = self._project_out(params, out, False, None)
        return y, cache

    def apply_paged(self, variables, x, pages_k, pages_v, block_tables,
                    offsets, layer=0, q_lens=None):
        """One step straight against the paged KV pool.

        The serving hot path (docs/serving.md): no contiguous cache is
        assembled (that is ``apply_cached``, the offline ``generate``'s); the
        new tokens' K/V rows are scattered into their pages and attention
        streams the pages the block table names
        (``ops.pallas.paged_attention``).

        x : (B, Q, D) — this step's new tokens per row (Q = 1 for pure
            decode; Q > 1 for ragged prefill chunks).
        pages_k / pages_v : the pool's (L, N, H_kv / p, bs, p * Dh) arrays;
            ``layer`` selects this block's slice without copying it.
        block_tables : (B, nb) page ids; offsets : (B,) the position each row
            writes first (its kv length BEFORE this step's tokens).
        q_lens : (B,) live tokens per row this step, or None for the decode
            form (Q must then be 1). Tokens past ``q_lens[b]`` are padding:
            their KV lands in the pool's scratch page and their outputs are
            garbage the caller must ignore.

        Returns (out (B, Q, D), pages_k, pages_v) — pages updated only at the
        written rows. The write rewrites the sublane tiles of the pages those
        rows land in (``scatter_kv_rows`` / ``scatter_kv_chunk``: 16 page
        rows of bf16 for a decode row; whole pages off the chip and for
        pages the kernel does not take, ``_kernel_writes``), so with the pool buffers donated through jit it is in
        place in the layout the kernel reads; it needs the engine's
        one-writer invariant (a step writes a non-scratch page from one row
        only).
        """
        if self.kv_cache_dtype == "int8":
            raise NotImplementedError(
                "paged decode with int8 KV pages is future work — pool pages "
                "are compute-dtype (see docs/serving.md limits)")
        params = variables["params"]
        q, k_new, v_new = self._project_qkv(params, x)   # (B, H*, Q, Dh)
        if self.rope_theta:
            with jax.named_scope("attn_qkv"):
                q = apply_rope(q, offsets, self.rope_theta)
                k_new = apply_rope(k_new, offsets, self.rope_theta)
        from ..ops.pallas import paged_attention as pa

        if self.window:
            out, pages_k, pages_v = self._eva_paged(
                params, q, k_new, v_new, pages_k, pages_v, block_tables,
                offsets, layer, q_lens)
            y = self._project_out(params, out, False, None)
            return y, pages_k, pages_v
        quant_pool = isinstance(pages_k, pa.QuantPages)
        if q_lens is None and x.shape[1] == 1:
            # decode form, kept verbatim: the pure-decode compiled step must
            # stay bit-identical to the pre-chunking program (QuantPages
            # skip the dtype cast — scatter quantizes the rows itself)
            rows_k, rows_v = k_new[:, :, 0], v_new[:, :, 0]
            if not quant_pool:
                rows_k = rows_k.astype(pages_k.dtype)
                rows_v = rows_v.astype(pages_v.dtype)
            pages_k = pa.scatter_kv_rows(pages_k, block_tables, offsets,
                                         rows_k, layer=layer)
            pages_v = pa.scatter_kv_rows(pages_v, block_tables, offsets,
                                         rows_v, layer=layer)
            out = pa.paged_attention(q[:, :, 0], pages_k, pages_v,
                                     block_tables, kv_lens=offsets + 1,
                                     layer=layer, scale=self.scale)
            y = self._project_out(params, out[:, :, None, :], False, None)
            return y, pages_k, pages_v
        if q_lens is None:
            raise ValueError("apply_paged with Q > 1 requires q_lens")
        # ragged chunk form: scatter the whole chunk's KV first, then attend
        # each row's live tokens against its own chunk + all prior positions
        chunk_k = k_new.transpose(0, 2, 1, 3)
        chunk_v = v_new.transpose(0, 2, 1, 3)
        if not quant_pool:
            chunk_k = chunk_k.astype(pages_k.dtype)
            chunk_v = chunk_v.astype(pages_v.dtype)
        pages_k = pa.scatter_kv_chunk(pages_k, block_tables, offsets, chunk_k,
                                      q_lens, layer=layer)
        pages_v = pa.scatter_kv_chunk(pages_v, block_tables, offsets, chunk_v,
                                      q_lens, layer=layer)
        out = pa.paged_attention(q.transpose(0, 2, 1, 3), pages_k, pages_v,
                                 block_tables, kv_lens=offsets + q_lens,
                                 q_lens=q_lens, layer=layer, scale=self.scale)
        y = self._project_out(params, out.transpose(0, 2, 1, 3), False, None)
        return y, pages_k, pages_v

    def _eva_paged(self, params, q, k_new, v_new, pages_k, pages_v,
                   block_tables, offsets, layer, q_lens):
        """The EVA step, one function for a decode row, a prompt chunk and a
        mixed batch of both: the (rotated) new rows go into the window's
        exact pages at their window-relative positions, every chunk they
        complete gets its summary row, and one softmax runs over the
        window's exact rows and the earlier windows' summaries. A step's
        tokens lie in one window (the scheduler ends a grant there).
        q, k_new, v_new: (B, H, Q, Dh); returns (out (B, H, Q, Dh), pages)."""
        from ..ops.pallas import eva_attention as eva
        from ..ops.pallas import paged_attention as pa

        if isinstance(pages_k, pa.QuantPages):
            raise NotImplementedError(
                "EVA summaries are written in the pool's compute dtype; "
                "int8 pages are refused for a windowed model")
        b, _, qw, _ = q.shape
        if q_lens is None:
            q_lens = jnp.full((b,), qw, jnp.int32)
        bs = pages_k.shape[-2]
        n_exact = self.window // bs
        rel = offsets % self.window
        exact = block_tables[:, :n_exact]
        pages_k = pa.scatter_kv_chunk(
            pages_k, exact, rel, k_new.transpose(0, 2, 1, 3).astype(
                pages_k.dtype), q_lens, layer=layer)
        pages_v = pa.scatter_kv_chunk(
            pages_v, exact, rel, v_new.transpose(0, 2, 1, 3).astype(
                pages_v.dtype), q_lens, layer=layer)
        pages_k, pages_v = eva.write_summaries(
            pages_k, pages_v, block_tables, offsets, q_lens, params["phi"],
            params["mu"], n_exact=n_exact, window=self.window,
            chunk=self.chunk, layer=layer, qw=qw)
        out = eva.eva_attention(
            q.transpose(0, 2, 1, 3), pages_k, pages_v, block_tables,
            rel + q_lens, (offsets // self.window) * (self.window
                                                      // self.chunk),
            n_exact=n_exact, q_lens=q_lens, layer=layer)
        return out.transpose(0, 2, 1, 3), pages_k, pages_v

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads, "causal": self.causal,
               "dropout": self.dropout, "backend": self.backend,
               "num_kv_heads": self.num_kv_heads,
               "kernel_init": initializers.name_of(self.kernel_init)}
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        if self.rope_theta:
            cfg["rope_theta"] = self.rope_theta
        if not self.use_bias:
            cfg["use_bias"] = False
        if self.window:
            cfg["window"], cfg["chunk"] = self.window, self.chunk
        if self.scale:
            cfg["scale"] = self.scale
        return cfg


# -- latent (MLA) attention -----------------------------------------------


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's ``dim / 2`` rotary frequencies (numpy, float32): the plain ones
    ``theta^(-2i/dim)`` for the pairs that turn more than ``beta_fast`` times
    over the ``original`` positions, those over ``factor`` for the pairs that
    turn less than ``beta_slow`` times, a linear ramp between the two pair
    indices in between."""
    import numpy as np

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def apply_rope_pairs(x, positions, inv_freq):
    """Rotary embedding over ADJACENT pairs ``(2i, 2i + 1)`` of the last dim
    (``rope_interleave``): x (..., S, d), positions (..., S) broadcastable
    against x's leading dims, ``inv_freq`` (d / 2,)."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _positions(offset, b, s):
    """(B, S) absolute positions of a step's tokens: ``offset`` a scalar or
    a (B,) array of each row's first position."""
    if getattr(offset, "ndim", 0):
        return offset[:, None] + jnp.arange(s)
    return jnp.broadcast_to(offset + jnp.arange(s), (b, s))


def _cache_writer(offset):
    """``upd(buf, new)``: new rows into a (B, H, T, .) cache at ``offset``,
    a scalar or a (B,) array of each row's own position."""
    if getattr(offset, "ndim", 0):
        return lambda buf, new: jax.vmap(
            lambda b, n, o: jax.lax.dynamic_update_slice_in_dim(
                b, n, o, axis=1))(buf, new, offset)
    return lambda buf, new: jax.lax.dynamic_update_slice_in_dim(
        buf, new, offset, axis=2)


class _ProjectAndNorm:
    """What the latent and the gated attention share: a bias-free product in
    the policy's dtypes and an RMSNorm with a plain gain (``norm_eps``)."""

    def _mm(self, x, w):
        from ..ops.pallas.quant_matmul import qmatmul

        return qmatmul(x, self.policy.cast_param(w)).astype(x.dtype)

    def _norm(self, x, gain, scale: float = 1.0, unit_offset: bool = False):
        """RMSNorm in float32, times ``scale`` before the cast back
        (``unit_offset``: the gain is ``1 + gain``)."""
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        g = gain.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(ms + self.norm_eps) * (1.0 + g if unit_offset
                                                      else g)
        return (y if scale == 1.0 else y * scale).astype(x.dtype)


@register_module("latent_attention")
class LatentAttention(_ProjectAndNorm, Module):
    """Multi-head latent attention (MLA) over (N, S, D): queries through a
    low-rank bottleneck, and ONE cached row a token, ``[c_kv | k_rope]``
    (``kv_rank + rope_dim`` values, no head axis), that every head's key and
    value are made from.

    ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` -> heads of ``[q_nope | q_rope]``;
    ``[c_kv | k_r] = x W_kva``, ``c_kv = norm(c_kv)``, ``k_rope = rope(k_r)``
    (one for all heads); ``[k_nope | v] = c_kv W_kvb`` a head. Rotary over
    adjacent pairs at YaRN frequencies (plain ``theta^(-2i/d)`` where the
    ``rope`` dict carries no ``factor``); the softmax scale is
    ``(nope + rope)^-1/2 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor)
    + 1``, and the query of position ``t`` is first scaled by ``1 +
    scaling_beta * ln(1 + floor(t / original))``.

    ``q_scale`` / ``kv_scale``: the rank scales of a model that multiplies
    every query head by ``(d_model / q_rank)^1/2`` and the normed ``c_kv`` by
    ``(d_model / kv_rank)^1/2``. Both are folded into the float32 norm of
    their latent (``W_qb`` is linear, so scaling ``c_q`` scales the heads):
    the row that is CACHED is ``kv_scale * norm(c_kv)``, and ``W_kvb``, the
    absorbed one too, is the checkpoint's.

    ``_apply`` / ``apply_cached`` are the plain EXPANDED form (keys and
    values of every head made from ``c_kv``). ``apply_paged`` is the
    ABSORBED form, for a decode row and a prompt chunk alike: ``W_kvb``'s key
    half is folded into the query and its value half into the output, so
    that a cached row is key (all of it) and value (its first ``kv_rank``
    values) at once, and ``ops.pallas.mla_attention`` reads each page ONCE.
    """

    def __init__(self, num_heads: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int, rope: dict,
                 norm_eps: float = 1e-6, backend: str = "xla",
                 q_scale: float = 1.0, kv_scale: float = 1.0, name=None,
                 policy=None):
        super().__init__(name=name, policy=policy)
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        self.num_heads, self.q_rank = int(num_heads), int(q_rank)
        self.kv_rank, self.nope_dim = int(kv_rank), int(nope_dim)
        self.rope_dim, self.v_dim = int(rope_dim), int(v_dim)
        self.rope = dict(rope)
        self.norm_eps = float(norm_eps)
        self.backend = backend
        r = self.rope
        self.original = int(r.get("original_max_position_embeddings", 1 << 30))
        self.scaling_beta = float(r.get("llama_4_scaling_beta", 0.0))
        factor = float(r.get("factor", 1.0))
        m = 0.1 * float(r.get("mscale_all_dim", 0.0)) * math.log(factor) + 1.0
        self.scale = (self.nope_dim + self.rope_dim) ** -0.5 * m * m
        if "factor" in r:
            self.inv_freq = yarn_inv_freq(
                self.rope_dim, float(r["rope_theta"]), factor, self.original,
                float(r.get("beta_fast", 32)), float(r.get("beta_slow", 1)))
        else:       # plain rotary: no ramp to take a frequency through
            import numpy as np

            self.inv_freq = (float(r["rope_theta"]) ** (-np.arange(
                0, self.rope_dim, 2, dtype=np.float64) / self.rope_dim)
                ).astype(np.float32)

    # what one cached row holds, and its width in the pool (whole lanes)
    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def latent_row(self) -> int:
        from ..ops.pallas.mla_attention import row_width

        return row_width(self.latent_dim)

    def _init(self, rng, input_shape):
        d, h = input_shape[-1], self.num_heads
        init = initializers.get("xavier_uniform")
        ks = jax.random.split(rng, 5)
        pd = self.policy.param_dtype
        return {
            "q_a_kernel": init(ks[0], (d, self.q_rank), pd),
            "q_norm": jnp.ones((self.q_rank,), pd),
            "q_b_kernel": init(
                ks[1], (self.q_rank, h * (self.nope_dim + self.rope_dim)), pd),
            "kv_a_kernel": init(ks[2], (d, self.latent_dim), pd),
            "kv_norm": jnp.ones((self.kv_rank,), pd),
            "kv_b_kernel": init(
                ks[3], (self.kv_rank, h * (self.nope_dim + self.v_dim)), pd),
            "out_kernel": init(ks[4], (h * self.v_dim, d), pd),
        }, {}

    @jax.named_scope("attn_qkv")
    def _latents(self, params, x, offset):
        """The two low-rank halves of a step's tokens at their positions:
        q (B, S, H, nope + rope), rotated and scaled by position; the cached
        row's two parts c_kv (B, S, kv_rank) and k_rope (B, S, rope)."""
        x = self.policy.cast_in(x)
        b, s, _ = x.shape
        pos = _positions(offset, b, s)
        q = self._mm(self._norm(self._mm(x, params["q_a_kernel"]),
                                params["q_norm"], self.q_scale),
                     params["q_b_kernel"])
        q = q.reshape(b, s, self.num_heads, self.nope_dim + self.rope_dim)
        q_rope = apply_rope_pairs(q[..., self.nope_dim:], pos[:, :, None],
                                  self.inv_freq)
        q = jnp.concatenate([q[..., :self.nope_dim], q_rope], axis=-1)
        if self.scaling_beta:
            q = (q.astype(jnp.float32) * (1.0 + self.scaling_beta * jnp.log1p(
                (pos // self.original).astype(jnp.float32)))[:, :, None, None]
                 ).astype(x.dtype)
        kv = self._mm(x, params["kv_a_kernel"])
        c_kv = self._norm(kv[..., :self.kv_rank], params["kv_norm"],
                          self.kv_scale)
        k_rope = apply_rope_pairs(kv[..., self.kv_rank:], pos, self.inv_freq)
        return q, c_kv, k_rope

    def _kv_b(self, params):
        """``W_kvb`` by head: key half (kv_rank, H, nope), value half
        (kv_rank, H, v)."""
        w = self.policy.cast_param(params["kv_b_kernel"]).reshape(
            self.kv_rank, self.num_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    @jax.named_scope("attn_qkv")
    def _expand(self, params, c_kv, k_rope):
        """Keys and values of every head from the cached row's parts:
        k (B, H, S, nope + rope), v (B, H, S, v)."""
        w_k, w_v = self._kv_b(params)
        k_nope = jnp.einsum("bsc,chd->bhsd", c_kv, w_k,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("bsc,chd->bhsd", c_kv, w_v,
                       preferred_element_type=jnp.float32)
        k_rope = jnp.broadcast_to(k_rope[:, None].astype(jnp.float32),
                                  k_nope.shape[:3] + (self.rope_dim,))
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        return k.astype(c_kv.dtype), v.astype(c_kv.dtype)

    @jax.named_scope("attn_out")
    def _project_out(self, params, heads):
        """heads (B, S, H, v) -> (B, S, D)."""
        b, s = heads.shape[:2]
        y = self._mm(heads.reshape(b, s, self.num_heads * self.v_dim),
                     params["out_kernel"])
        return self.policy.cast_out(y)

    def _apply(self, params, state, x, *, train, rng):
        q, c_kv, k_rope = self._latents(params, x, 0)
        k, v = self._expand(params, c_kv, k_rope)
        out = sdpa(q.transpose(0, 2, 1, 3), k, v, causal=True,
                   scale=self.scale, backend="xla")
        return self._project_out(params, out.transpose(0, 2, 1, 3)), state

    # -- cached decode, expanded (the offline ``generate``) ----------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        dtype = self.policy.compute_dtype
        h = self.num_heads
        return {"k": jnp.zeros((batch, h, max_len,
                                self.nope_dim + self.rope_dim), dtype),
                "v": jnp.zeros((batch, h, max_len, self.v_dim), dtype)}

    def apply_cached(self, variables, x, cache, offset):
        params = variables["params"]
        q, c_kv, k_rope = self._latents(params, x, offset)
        k_new, v_new = self._expand(params, c_kv, k_rope)
        upd = _cache_writer(offset)
        with jax.named_scope("kv_write"):
            cache = {"k": upd(cache["k"], k_new), "v": upd(cache["v"], v_new)}
        out = sdpa(q.transpose(0, 2, 1, 3), cache["k"], cache["v"],
                   causal=True, scale=self.scale, kv_offset=offset,
                   backend="xla")
        return self._project_out(params, out.transpose(0, 2, 1, 3)), cache

    # -- the serving step, absorbed -----------------------------------------

    def apply_paged(self, variables, x, pages_k, pages_v, block_tables,
                    offsets, layer=0, q_lens=None):
        """One step against the pool's latent pages ``(L, N, 1, bs,
        latent_row)``: x (B, Q, D) with ``q_lens[b]`` live tokens a row
        (None: the decode form, every row one token). The new rows ``[c_kv |
        k_rope | 0]`` are written into their pages, then every head's
        absorbed query ``[q_nope W_uk^T | q_rope | 0]`` attends over the
        row's pages, whose first ``kv_rank`` values are the values too; ``W_uv``
        and ``W_o`` follow. ``pages_v`` is the pool's unallocated stub and
        passes through. Returns (out (B, Q, D), pages_k, pages_v)."""
        from ..ops.pallas import mla_attention as mla
        from ..ops.pallas import paged_attention as pa

        params = variables["params"]
        b, qw, _ = x.shape
        if q_lens is None:
            q_lens = jnp.ones((b,), jnp.int32)
        q, c_kv, k_rope = self._latents(params, x, offsets)
        w_k, w_v = self._kv_b(params)
        pad = pages_k.shape[-1] - self.latent_dim
        with jax.named_scope("attn_qkv"):
            rows = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((b, qw, pad), c_kv.dtype)], axis=-1)
            q_abs = jnp.einsum("bqhd,chd->bqhc", q[..., :self.nope_dim], w_k,
                               preferred_element_type=jnp.float32)
            q_lat = jnp.concatenate(
                [q_abs.astype(q.dtype), q[..., self.nope_dim:],
                 jnp.zeros((b, qw, self.num_heads, pad), q.dtype)], axis=-1)
        pages_k = pa.scatter_kv_chunk(
            pages_k, block_tables, offsets,
            rows[:, :, None].astype(pages_k.dtype), q_lens, layer=layer)
        out = mla.mla_attention(
            q_lat.astype(pages_k.dtype), pages_k, block_tables,
            offsets + q_lens, q_lens=q_lens, layer=layer, scale=self.scale,
            value_dim=self.kv_rank)
        with jax.named_scope("attn_out"):
            heads = jnp.einsum("bqhc,chd->bqhd", out.astype(w_v.dtype), w_v,
                               preferred_element_type=jnp.float32)
        y = self._project_out(params, heads.astype(c_kv.dtype))
        return y, pages_k, pages_v

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads, "q_rank": self.q_rank,
               "kv_rank": self.kv_rank, "nope_dim": self.nope_dim,
               "rope_dim": self.rope_dim, "v_dim": self.v_dim,
               "rope": self.rope, "norm_eps": self.norm_eps,
               "backend": self.backend}
        for key in ("q_scale", "kv_scale"):
            if getattr(self, key) != 1.0:
                cfg[key] = getattr(self, key)
        return cfg


# -- gated grouped-query attention, sliding or global -----------------------

# key positions a grid step of the paged kernel covers for this attention:
# its pages hold 128 positions of 128 lanes, and a step that fetched one
# such page a KV head would spend as long starting as moving (a grid step
# costs ~0.35 us whatever it holds; ops/pallas/paged_attention.fetch_group)
GATED_GROUP_POSITIONS = 512


@register_module("gated_attention")
class GatedAttention(_ProjectAndNorm, Module):
    """Grouped-query attention over (N, S, D) with heads of ``head_dim``
    (``num_heads * head_dim`` need not be D), an RMSNorm of every query and
    key head, a sigmoid gate on the output, and ONE of two kinds a layer:

    ``window`` set: each position attends itself and the ``window - 1``
    before it, and queries and keys are rotated (``rope_theta``, half
    rotation over the whole head);
    ``window`` None: every earlier position, and NO positions at all,
    unless the layer is given a ``rope_theta`` all the same.

    ``rotary_dim``: only the FIRST ``rotary_dim`` lanes of a head are rotated
    (a partial rotary factor); ``norm_unit_offset``: the two head norms' gain
    is ``1 + w``.

    ``[q | k | v | g] = x W_qkvg``; ``q, k = rms_q(q), rms_k(k)`` a head;
    ``o = softmax(q k^T / sqrt(head_dim)) v * sigmoid(g)``; ``y = o W_o``.

    ``apply_paged`` serves both kinds from the pool's pages through
    ``ops.pallas.paged_attention`` (its ``window`` and ``table_base``): a
    window layer's table lists only the pages the row still holds."""

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                 window: Optional[int] = None,
                 rope_theta: Optional[float] = None, norm_eps: float = 1e-5,
                 backend: str = "xla", rotary_dim: Optional[int] = None,
                 norm_unit_offset: bool = False, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.rotary_dim = int(rotary_dim) if rotary_dim else None
        self.norm_unit_offset = bool(norm_unit_offset)
        if self.num_kv_heads <= 0 or self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads {num_kv_heads} must be a "
                             f"positive divisor of num_heads {num_heads}")
        self.window = int(window) if window else None
        self.rope_theta = float(rope_theta) if rope_theta else None
        self.norm_eps = float(norm_eps)
        self.backend = backend

    def _init(self, rng, input_shape):
        d, dh = input_shape[-1], self.head_dim
        wide = (2 * self.num_heads + 2 * self.num_kv_heads) * dh
        init = initializers.get("xavier_uniform")
        k1, k2 = jax.random.split(rng)
        pd = self.policy.param_dtype
        return {"qkvg_kernel": init(k1, (d, wide), pd),
                "q_norm": jnp.ones((dh,), pd), "k_norm": jnp.ones((dh,), pd),
                "out_kernel": init(k2, (self.num_heads * dh, d), pd)}, {}

    @jax.named_scope("attn_qkv")
    def _project(self, params, x, offset):
        """q (B, H, S, Dh), k, v (B, H_kv, S, Dh), gate (B, S, H * Dh) of a
        step's tokens, q and k normed and (a window layer's) rotated at
        their positions."""
        x = self.policy.cast_in(x)
        b, s, _ = x.shape
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        q, k, v, gate = jnp.split(
            self._mm(x, params["qkvg_kernel"]),
            [h * dh, (h + hkv) * dh, (h + 2 * hkv) * dh], axis=-1)
        unit = self.norm_unit_offset
        q = self._norm(q.reshape(b, s, h, dh), params["q_norm"],
                       unit_offset=unit)
        k = self._norm(k.reshape(b, s, hkv, dh), params["k_norm"],
                       unit_offset=unit)
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        if self.rope_theta and self.rotary_dim:
            q, k = (jnp.concatenate(
                [apply_rope(t[..., :self.rotary_dim], offset,
                            self.rope_theta), t[..., self.rotary_dim:]],
                axis=-1) for t in (q, k))
        elif self.rope_theta:
            q = apply_rope(q, offset, self.rope_theta)
            k = apply_rope(k, offset, self.rope_theta)
        return q, k, v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3), gate

    @jax.named_scope("attn_out")
    def _project_out(self, params, heads, gate):
        """heads (B, S, H, Dh), gate (B, S, H * Dh) -> (B, S, D)."""
        b, s = heads.shape[:2]
        o = heads.reshape(b, s, -1)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        return self.policy.cast_out(self._mm(o, params["out_kernel"]))

    def _seen(self, q_pos, k_pos):
        """(.., Sq, Sk) bool: what a query at ``q_pos`` attends."""
        seen = k_pos[..., None, :] <= q_pos[..., :, None]
        if self.window:
            seen &= q_pos[..., :, None] - k_pos[..., None, :] < self.window
        return seen

    def _apply(self, params, state, x, *, train, rng):
        q, k, v, gate = self._project(params, x, 0)
        pos = jnp.arange(x.shape[1])
        out = sdpa(q, k, v, mask=self._seen(pos, pos)[None, None],
                   backend="xla")
        return self._project_out(params, out.transpose(0, 2, 1, 3),
                                 gate), state

    # -- cached decode (the offline ``generate``) --------------------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        z = jnp.zeros((batch, self.num_kv_heads, max_len, self.head_dim),
                      self.policy.compute_dtype)
        return {"k": z, "v": z}

    def apply_cached(self, variables, x, cache, offset):
        params = variables["params"]
        q, k_new, v_new, gate = self._project(params, x, offset)
        upd = _cache_writer(offset)
        with jax.named_scope("kv_write"):
            cache = {"k": upd(cache["k"], k_new), "v": upd(cache["v"], v_new)}
        b, s = x.shape[:2]
        seen = self._seen(_positions(offset, b, s),
                          jnp.arange(cache["k"].shape[2])[None])
        out = sdpa(q, cache["k"], cache["v"], mask=seen[:, None],
                   backend="xla")
        return self._project_out(params, out.transpose(0, 2, 1, 3),
                                 gate), cache

    # -- the serving step ----------------------------------------------------

    def apply_paged(self, variables, x, pages_k, pages_v, block_tables,
                    offsets, layer=0, q_lens=None, table_base=None):
        """One step against the pool's pages: x (B, Q, D) with ``q_lens[b]``
        live tokens a row (None: the decode form, every row one token) at
        positions ``offsets[b] ..``. ``block_tables`` (B, nb) are THIS
        layer's pages; entry 0 is the row's logical page ``table_base[b]``
        (None: page 0), so a window layer's rows are written and read
        relative to the pages they still hold. Returns (out (B, Q, D),
        pages_k, pages_v)."""
        from ..ops.pallas import paged_attention as pa

        params = variables["params"]
        b = x.shape[0]
        if q_lens is None:
            q_lens = jnp.ones((b,), jnp.int32)
        q, k_new, v_new, gate = self._project(params, x, offsets)
        bs = pages_k.shape[-2]
        at = offsets if table_base is None else offsets - table_base * bs
        pages_k = pa.scatter_kv_chunk(
            pages_k, block_tables, at,
            k_new.transpose(0, 2, 1, 3).astype(pages_k.dtype), q_lens,
            layer=layer)
        pages_v = pa.scatter_kv_chunk(
            pages_v, block_tables, at,
            v_new.transpose(0, 2, 1, 3).astype(pages_v.dtype), q_lens,
            layer=layer)
        with jax.named_scope("win_attn" if self.window else "full_attn"):
            out = pa.paged_attention(
                q.transpose(0, 2, 1, 3), pages_k, pages_v, block_tables,
                kv_lens=offsets + q_lens, q_lens=q_lens, layer=layer,
                window=self.window, table_base=table_base,
                group_positions=GATED_GROUP_POSITIONS)
        return self._project_out(params, out, gate), pages_k, pages_v

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads,
               "num_kv_heads": self.num_kv_heads, "head_dim": self.head_dim,
               "window": self.window, "rope_theta": self.rope_theta,
               "norm_eps": self.norm_eps, "backend": self.backend}
        if self.rotary_dim:
            cfg["rotary_dim"] = self.rotary_dim
        if self.norm_unit_offset:
            cfg["norm_unit_offset"] = True
        return cfg


# -- mixers that keep a STATE, no cache of positions ------------------------

# a serving step whose row starts at a multiple of this many positions keeps
# the state it read in one of the row's two snapshot slots, by turns: what a
# roll-back of the overlapped loop restores (``serving.kv_pool.StateSlots``)
SNAPSHOT_EVERY = 16


def snapshot_slots(slots, offsets, xp=jnp):
    """(B,) the slot of the snapshot arrays that takes the state a step's row
    READ: row ``b`` of slot ``slots[b]`` (0: padding) that starts at position
    ``offsets[b]``; 0, the dump slot, where the row keeps nothing. A row's two
    snapshots lie at ``2 * (slot - 1) + 1`` and ``+ 2`` and take turns. The
    host keeps the same count by the same rule (``xp=numpy``:
    ``serving.engine._note_snapshots``)."""
    turn = (offsets // SNAPSHOT_EVERY) % 2
    keep = (slots > 0) & (offsets % SNAPSHOT_EVERY == 0)
    return xp.where(keep, 2 * (slots - 1) + turn + 1, 0).astype(xp.int32)


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def _decay_init(k_a, k_dt, heads: int, a_min: float):
    """(``A_log``, ``dt_bias``) (heads,) float32 in the initial ranges both
    state mixers' families publish: ``A`` uniform in [a_min, 16), the step
    ``dt`` log-uniform in [1e-3, 0.1] through the inverse softplus."""
    return (jnp.log(jax.random.uniform(k_a, (heads,), jnp.float32, a_min,
                                       16.0)),
            _inv_softplus(jnp.exp(jax.random.uniform(
                k_dt, (heads,), jnp.float32, math.log(1e-3),
                math.log(0.1)))))


class _StateMixer(_ProjectAndNorm):
    """What the mixers share whose memory of the context is a STATE updated
    in place at every position, and the ONE protocol the serving path asks
    of either (``serving.kv_pool.StateSlots``, ``models.llama``):

      ``conv_rows``    the shape a slot's ``conv - 1`` kept positions of
                       ``channels`` values rest in (the compute dtype)
      ``rec_shape``    a row's recurrent state in one layer, float32
      ``apply_state``  one step against the pool's state slots

    A mixer is: a projection (``_project`` -> ``u`` the convolution's
    channels, ``z`` the output gate, and what else its recurrence reads), a
    causal depthwise convolution of ``conv`` taps then SiLU, a recurrence in
    float32 (``_heads`` -> its operands a head; ``_step``: ONE position a row
    against the slots, a kernel on the chip; ``_closed_form``: a chunk of
    positions from a gathered state, ``_ops().SUB`` positions a sub-chunk;
    ``_ops`` is the mixer's module of ``ops.pallas``, imported late), and
    ``_project_out``. Its four scopes in a device profile are ``scope`` +
    ``_proj`` / ``_conv`` / ``_state`` / ``_out`` (docs/observability.md)."""

    scope = ""

    def _convolve(self, params, u, conv0, q_lens):
        """The causal depthwise convolution of u (B, Q, C) behind the
        ``conv - 1`` positions ``conv0`` (B, conv - 1, C) kept from before
        (plus ``conv_bias`` where the mixer has one), then SiLU; and the
        positions to keep for the next step: the last ``conv - 1`` before
        position ``q_lens[b]`` (None: all ``Q`` live)."""
        with jax.named_scope(self.scope + "_conv"):
            taps = self.conv
            qw = u.shape[1]
            ext = jnp.concatenate([conv0.astype(u.dtype), u], axis=1)
            w = params["conv_kernel"].astype(jnp.float32)
            c = sum(w[j] * ext[:, j:j + qw].astype(jnp.float32)
                    for j in range(taps))
            if "conv_bias" in params:
                c = c + params["conv_bias"].astype(jnp.float32)
            if q_lens is None:
                kept = ext[:, qw:]
            else:
                at = q_lens[:, None] + jnp.arange(taps - 1)[None, :]
                kept = jnp.take_along_axis(ext, at[:, :, None], axis=1)
            return jax.nn.silu(c), kept

    def _scan(self, params, parts, rec0):
        """``_closed_form`` over a chunk of any width: past ``SUB``
        positions the chunk is padded to whole sub-chunks (a padding
        position, all zeros, leaves the state alone)."""
        qw, sub = parts[0].shape[1], self._ops().SUB
        pad = -qw % sub if qw > sub else 0
        if pad:
            parts = tuple(jnp.pad(
                t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in parts)
        o, rec1 = self._closed_form(params, parts, rec0)
        return o[:, :qw], rec1

    def _mix(self, params, x, conv0, rec0, q_lens=None):
        """The layer over a chunk from the state (conv0, rec0) a row: (y (B,
        Q, D), the conv positions and the states to keep)."""
        u, z, *gates = self._project(params, x)
        c, conv1 = self._convolve(params, u, conv0, q_lens)
        with jax.named_scope(self.scope + "_state"):
            o, rec1 = self._scan(
                params, self._heads(params, c, *gates, q_lens), rec0)
        return self._project_out(params, o, z), conv1, rec1

    @property
    def conv_rows(self):
        """The shape a slot's ``conv - 1`` kept positions REST in: whole
        (16, 128) registers of two-byte values, 512 lanes wide, where they
        fill them, else rows of 128 lanes where they fill those (the pool's
        array is then gathered and scattered a slot at a time in the layout
        it rests in, with no copy of it: three rows of positions are no
        whole tile, and the compiler would lay the array out anew at a
        program's entry and exit); else as they are, ``(conv - 1,
        channels)``."""
        total = (self.conv - 1) * self.channels
        if total % (16 * 512) == 0:
            return (total // 512, 512)
        if total % 128 == 0:
            return (total // 128, 128)
        return (self.conv - 1, self.channels)

    def _zero_state(self, batch: int):
        return (jnp.zeros((batch, self.conv - 1, self.channels),
                          self.policy.compute_dtype),
                jnp.zeros((batch,) + self.rec_shape, jnp.float32))

    def _apply(self, params, state, x, *, train, rng):
        y, _, _ = self._mix(params, x, *self._zero_state(x.shape[0]))
        return y, state

    # -- cached decode (the offline ``generate``): the cache IS the state --

    def init_cache(self, batch: int, max_len: int, d_model: int):
        conv, rec = self._zero_state(batch)
        return {"conv": conv, "rec": rec}

    def apply_cached(self, variables, x, cache, offset):
        y, conv, rec = self._mix(variables["params"], x, cache["conv"],
                                 cache["rec"])
        return y, {"conv": conv, "rec": rec}

    # -- the serving step -----------------------------------------------------

    def apply_state(self, variables, x, state, slots, offsets, layer: int,
                    q_lens=None):
        """One step against the pool's state slots (``serving.kv_pool``:
        ``conv`` (L, S) + ``conv_rows``, ``rec`` (L, S) + ``rec_shape``
        float32, and the snapshots ``conv_snap`` / ``rec_snap``): x (B, Q,
        D) with ``q_lens[b]`` live tokens a row (None: the decode form,
        every row ONE token) from position ``offsets[b]``, row ``b``'s state
        in slot ``slots[b]``. A row at position 0 starts from zeros whatever
        its slot holds; a row at a multiple of ``SNAPSHOT_EVERY`` keeps the
        state it read (``snapshot_slots``). Returns (y (B, Q, D), state)."""
        params = variables["params"]
        snaps = snapshot_slots(slots, offsets)
        fresh = (offsets == 0)
        u, z, *gates = self._project(params, x)
        with jax.named_scope(self.scope + "_conv"):
            conv0 = state["conv"][layer, slots]
            conv0 = jnp.where(fresh[:, None, None], 0, conv0)
            state = dict(state, conv_snap=state["conv_snap"].at[
                layer, snaps].set(conv0))
            conv0 = conv0.reshape(-1, self.conv - 1, self.channels)
        c, conv1 = self._convolve(params, u, conv0, q_lens)
        with jax.named_scope(self.scope + "_conv"):
            state["conv"] = state["conv"].at[layer, slots].set(
                conv1.astype(state["conv"].dtype).reshape(
                    (-1,) + self.conv_rows))
        with jax.named_scope(self.scope + "_state"):
            parts = self._heads(params, c, *gates, q_lens)
            if q_lens is None:
                o, rec, snap = self._step(
                    params, tuple(t[:, 0] for t in parts), state["rec"],
                    state["rec_snap"], slots, snaps, layer)
                o = o[:, None]
            else:
                rec0 = jnp.where(fresh[:, None, None, None], 0.0,
                                 state["rec"][layer, slots])
                snap = state["rec_snap"].at[layer, snaps].set(rec0)
                o, rec1 = self._scan(params, parts, rec0)
                rec = state["rec"].at[layer, slots].set(rec1)
            state.update(rec=rec, rec_snap=snap)
        return self._project_out(params, o, z), state

    def output_shape(self, input_shape):
        return tuple(input_shape)


@register_module("gated_delta_net")
class GatedDeltaNet(_StateMixer, Module):
    """Gated DeltaNet over (N, S, D): linear attention whose memory of the
    context is a STATE updated in place at every position, not a cache that
    grows: ``key_heads`` query/key heads of ``key_dim``, ``value_heads``
    value heads of ``value_dim`` (each key head serves ``value_heads /
    key_heads`` of them), a causal depthwise convolution of ``conv`` taps in
    front.

        [q | k | v | z] = x W_qkvz;   [b | a] = x W_ba
        [q | k | v] <- silu(conv_t([q | k | v]))     c_t = sum_j w_j u_{t-conv+1+j}
        q <- q / |q| * key_dim^-1/2;  k <- k / |k|   (a head)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (float32)
        S <- e^g S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
        y = (w * o * rsqrt(mean o^2 + eps) * silu(z)) W_o        (a head)

    What a row carries from one step to the next is ``conv - 1`` positions of
    ``[q | k | v]`` before the convolution (the policy's compute dtype) and
    ``S``, (value_heads, key_dim, value_dim) FLOAT32. ``_apply`` and
    ``apply_cached`` run the chunked closed form from a zero (or the cache's)
    state; ``apply_state`` (``_StateMixer``) is the serving step against the
    pool's state slots: a decode step through ``ops.pallas.gdn_step`` (one
    read and one write of each state), a prompt chunk through the chunked
    form.

    Leaves: ``qkvz_kernel`` (D, 2 key_heads key_dim + 2 value_heads
    value_dim), ``ba_kernel`` (D, 2 value_heads), ``conv_kernel`` (conv,
    channels), ``A_log`` and ``dt_bias`` (value_heads,) float32, ``norm``
    (value_dim,), ``out_kernel`` (value_heads value_dim, D)."""

    scope = "gdn"

    def __init__(self, key_heads: int, value_heads: int, key_dim: int,
                 value_dim: int, conv: int = 4, norm_eps: float = 1e-6,
                 name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.key_heads, self.value_heads = int(key_heads), int(value_heads)
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.conv, self.norm_eps = int(conv), float(norm_eps)
        if self.value_heads % self.key_heads:
            raise ValueError(f"value_heads {value_heads} is a multiple of "
                             f"key_heads {key_heads}")
        self.qk = self.key_heads * self.key_dim
        self.vz = self.value_heads * self.value_dim
        self.channels = 2 * self.qk + self.vz       # what the conv runs over
        self.rec_shape = (self.value_heads, self.key_dim, self.value_dim)

    def _init(self, rng, input_shape):
        d, hv = input_shape[-1], self.value_heads
        init = initializers.get("xavier_uniform")
        ks = jax.random.split(rng, 6)
        pd = self.policy.param_dtype
        a_log, dt_bias = _decay_init(ks[3], ks[4], hv, 1e-3)
        return {
            "qkvz_kernel": init(ks[0], (d, self.channels + self.vz), pd),
            "ba_kernel": init(ks[1], (d, 2 * hv), pd),
            "conv_kernel": (jax.random.normal(
                ks[2], (self.conv, self.channels), jnp.float32)
                / math.sqrt(self.conv)).astype(pd),
            "A_log": a_log,
            "dt_bias": dt_bias,
            "norm": jnp.ones((self.value_dim,), pd),
            "out_kernel": init(ks[5], (self.vz, d), pd),
        }, {}

    @jax.named_scope("gdn_proj")
    def _project(self, params, x):
        """x (B, Q, D) -> u (B, Q, channels) ``[q | k | v]`` before the
        convolution, z (B, Q, vz), b and a (B, Q, value_heads)."""
        x = self.policy.cast_in(x)
        qkvz = self._mm(x, params["qkvz_kernel"])
        ba = self._mm(x, params["ba_kernel"])
        hv = self.value_heads
        return (qkvz[..., :self.channels], qkvz[..., self.channels:],
                ba[..., :hv], ba[..., hv:])

    def _heads(self, params, c, b_raw, a_raw, q_lens):
        """The convolved channels as what the rule reads, a VALUE head each,
        float32: q, k (B, Q, Hv, Dk) normed (q scaled), v (B, Q, Hv, Dv), g
        and beta (B, Q, Hv). A padding position (past ``q_lens``) gets ``g``
        = 0 and ``beta`` = 0: it leaves the state as it was."""
        b, qw = c.shape[:2]
        hk, hv, dk = self.key_heads, self.value_heads, self.key_dim

        def unit(t):
            t = t.reshape(b, qw, hk, dk)
            t = t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + 1e-6)
            return jnp.repeat(t, hv // hk, axis=2)

        q = unit(c[..., :self.qk]) * dk ** -0.5
        k = unit(c[..., self.qk:2 * self.qk])
        v = c[..., 2 * self.qk:].reshape(b, qw, hv, self.value_dim)
        beta = jax.nn.sigmoid(b_raw.astype(jnp.float32))
        g = -jnp.exp(params["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
        if q_lens is not None:
            live = (jnp.arange(qw)[None, :] < q_lens[:, None])[..., None]
            g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
        return q, k, v, g, beta

    @staticmethod
    def _ops():
        from ..ops.pallas import gdn_step

        return gdn_step

    def _step(self, params, parts, rec, snap, slots, snaps, layer):
        return self._ops().gdn_step(*parts, rec, snap, slots, snaps,
                                    layer=layer)

    def _closed_form(self, params, parts, rec0):
        ops = self._ops()
        return ops.gdn_chunk(*parts, rec0, sub=ops.SUB)

    @jax.named_scope("gdn_out")
    def _project_out(self, params, o, z):
        """o (B, Q, Hv, Dv) float32, z (B, Q, vz): the gated norm a head,
        then ``W_o``."""
        b, qw = o.shape[:2]
        ms = jnp.mean(o * o, axis=-1, keepdims=True)
        y = o * jax.lax.rsqrt(ms + self.norm_eps) \
            * params["norm"].astype(jnp.float32)
        y = y.reshape(b, qw, self.vz) * jax.nn.silu(z.astype(jnp.float32))
        return self.policy.cast_out(
            self._mm(y.astype(z.dtype), params["out_kernel"]))

    def _config(self):
        return {"key_heads": self.key_heads, "value_heads": self.value_heads,
                "key_dim": self.key_dim, "value_dim": self.value_dim,
                "conv": self.conv, "norm_eps": self.norm_eps}


@register_module("mamba2")
class Mamba2(_StateMixer, Module):
    """A Mamba-2 (state-space duality) mixer over (N, S, D): ``heads`` heads
    of ``head_dim`` (``d_inner = heads head_dim``, the model's ``expand``
    times D), each with a state ``(head_dim, state)`` float32 that a scalar
    decay a head shrinks and an outer product grows; ``B`` and ``C`` (state,)
    are shared by all heads (ONE group); a causal depthwise convolution of
    ``conv`` taps WITH a bias over ``[x | B | C]`` in front.

        [z | xBC | dt] = x W_in
        [x | B | C] <- silu(conv_t(xBC) + b_c)
        D_t = softplus(dt + dt_bias);  a_t = exp(-exp(A_log) D_t)   (a head)
        S <- a_t S + (D_t x_t) B_t^T;  y_t = S C_t + D x_t
        out = (w * g rsqrt(mean g^2 + eps)) W_out,  g = y * silu(z)

    The gate comes BEFORE the norm, and the norm runs over all ``d_inner``
    channels. Nothing clamps the step (the family's ``time_step_limit`` is
    (0, inf)). What a row carries from one step to the next is ``conv - 1``
    positions of ``xBC`` before the convolution and ``S``; the serving step
    (``_StateMixer.apply_state``) runs a decode row through
    ``ops.pallas.mamba2_step`` (one read and one write of each state) and a
    prompt chunk through the chunked closed form (``ssd_chunk``).

    Leaves: ``in_kernel`` (D, 2 d_inner + 2 state + heads) laid ``[z | xBC |
    dt]``, ``conv_kernel`` (conv, d_inner + 2 state), ``conv_bias``,
    ``A_log``, ``dt_bias`` and ``D`` (heads,) float32, ``norm`` (d_inner,),
    ``out_kernel`` (d_inner, D)."""

    scope = "ssm"

    def __init__(self, heads: int, head_dim: int, state: int, conv: int = 4,
                 norm_eps: float = 1e-5, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.state, self.conv = int(state), int(conv)
        self.norm_eps = float(norm_eps)
        self.inner = self.heads * self.head_dim
        self.channels = self.inner + 2 * self.state  # what the conv runs over
        self.rec_shape = (self.heads, self.head_dim, self.state)

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        init = initializers.get("xavier_uniform")
        ks = jax.random.split(rng, 5)
        pd = self.policy.param_dtype
        a_log, dt_bias = _decay_init(ks[2], ks[3], self.heads, 1.0)
        return {
            "in_kernel": init(ks[0], (d, self.inner + self.channels
                                      + self.heads), pd),
            "conv_kernel": (jax.random.normal(
                ks[1], (self.conv, self.channels), jnp.float32)
                / math.sqrt(self.conv)).astype(pd),
            "conv_bias": jnp.zeros((self.channels,), pd),
            "A_log": a_log,
            "dt_bias": dt_bias,
            "D": jnp.ones((self.heads,), jnp.float32),
            "norm": jnp.ones((self.inner,), pd),
            "out_kernel": init(ks[4], (self.inner, d), pd),
        }, {}

    @jax.named_scope("ssm_proj")
    def _project(self, params, x):
        """x (B, Q, D) -> u (B, Q, channels) ``[x | B | C]`` before the
        convolution, z (B, Q, d_inner), dt (B, Q, heads)."""
        zxd = self._mm(self.policy.cast_in(x), params["in_kernel"])
        at = self.inner + self.channels
        return zxd[..., self.inner:at], zxd[..., :self.inner], zxd[..., at:]

    def _heads(self, params, c, dt_raw, q_lens):
        """The convolved channels as what the recurrence reads, float32: x
        (B, Q, H, P), the step dt and the log decay la (B, Q, H), B and C
        (B, Q, N). A padding position (past ``q_lens``) gets ``dt`` = 0, so
        ``la`` = 0: it leaves the state as it was."""
        b, qw = c.shape[:2]
        x = c[..., :self.inner].reshape(b, qw, self.heads, self.head_dim)
        bm = c[..., self.inner:self.inner + self.state]
        cm = c[..., self.inner + self.state:]
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))
        if q_lens is not None:
            live = (jnp.arange(qw)[None, :] < q_lens[:, None])[..., None]
            dt = jnp.where(live, dt, 0.0)
        la = -jnp.exp(params["A_log"].astype(jnp.float32)) * dt
        return x, dt, la, bm, cm

    def _skip(self, params, y, x):
        return y + params["D"].astype(jnp.float32)[:, None] * x

    @staticmethod
    def _ops():
        from ..ops.pallas import mamba2_step

        return mamba2_step

    def _step(self, params, parts, rec, snap, slots, snaps, layer):
        y, rec, snap = self._ops().mamba2_step(*parts, rec, snap, slots,
                                               snaps, layer=layer)
        return self._skip(params, y, parts[0]), rec, snap

    def _closed_form(self, params, parts, rec0):
        ops = self._ops()
        y, rec1 = ops.ssd_chunk(*parts, rec0, sub=ops.SUB)
        return self._skip(params, y, parts[0]), rec1

    @jax.named_scope("ssm_out")
    def _project_out(self, params, y, z):
        """y (B, Q, H, P) float32, z (B, Q, d_inner): the gate, THEN one
        norm over all ``d_inner`` channels, then ``W_out``."""
        b, qw = y.shape[:2]
        g = y.reshape(b, qw, self.inner) * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.norm_eps) \
            * params["norm"].astype(jnp.float32)
        return self.policy.cast_out(
            self._mm(g.astype(z.dtype), params["out_kernel"]))

    def _config(self):
        return {"heads": self.heads, "head_dim": self.head_dim,
                "state": self.state, "conv": self.conv,
                "norm_eps": self.norm_eps}


# the mixers a model's ``linear`` keywords may name (``mixer``), by the name
# each is registered under
STATE_MIXERS = {"gated_delta_net": GatedDeltaNet, "mamba2": Mamba2}


def state_mixer(linear: dict, **kw):
    """The state mixer a model's ``linear`` keywords describe: ``mixer``
    names its class (absent: Gated DeltaNet), the rest are its own."""
    linear = dict(linear)
    return STATE_MIXERS[linear.pop("mixer", "gated_delta_net")](**linear, **kw)
