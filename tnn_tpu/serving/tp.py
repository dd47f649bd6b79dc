"""Tensor-parallel serving: shard the decode/prefill hot path over a TP mesh.

Single-replica tensor parallelism for the inference engine (docs/serving.md,
"Tensor-parallel serving"): attention heads are split over the mesh's
``model`` axis, the paged KV pool is split head-wise so each shard owns
``H_kv/tp`` heads of EVERY block, and each shard sweeps its own pages with
the unmodified ragged paged-attention kernel. Exactly two all-reduces per
layer (attention out-projection, MLP down-projection) rebuild the replicated
residual stream; everything outside the per-head math — embeddings, layer
norms, the LM head, sampling — runs replicated on every shard, so the
engine's host-side bookkeeping (block tables, refcounts, prefix-cache index,
scheduler) is untouched: block-table math never looks inside a bundle.

Layout (shard s of tp):

    qkv_kernel  (D, D + 2*KVD)   columns, head-permuted   -> P(None, model)
    qkv_bias    (D + 2*KVD,)     same permutation         -> P(model)
    out_kernel  (D, D)           rows (head-major)        -> P(model, None)
    fc/kernel   (D, 4D)          columns                  -> P(None, model)
    fc/bias     (4D,)            columns                  -> P(model)
    proj/kernel (4D, D)          rows                     -> P(model, None)
    out_bias / proj/bias / ln* / wte / wpe                -> replicated
    pages_k / pages_v  (L, N, H_kv/p, bs, p*Dh)  axis 2  -> P(None, None, model)

Axis 2 of the pool holds page ROWS, ``p`` KV heads side by side
(``kv_pool.PagedKVPool.lane_pack``). The pool picks ``p`` from the heads ONE
shard holds (``sharding.shard_shape``), so a shard always owns whole rows:
2 of 4 heads a shard pack in pairs, an odd 5 of GPT-2 large's 20 at ``tp`` =
4 stay unpacked (the cost of a pool of rows of 64, not an error).

The fused qkv kernel's columns are laid out ``[q | k | v]`` with heads
contiguous inside each section, so a flat column split would hand shard 0 a
slab of q columns only. ``_permute_qkv`` reorders the columns to
``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]`` (one group per shard, heads intact)
once at load time; after that a plain ``P(None, "model")`` chunking is
head-aligned and the in-step split/reshape math is identical to the
single-chip module with local head counts.

Exactness contract (tested token-exact in tests/test_tp_serving.py): the qkv
and fc matmuls contract over the full, unsharded axis — bit-identical per
shard. Per-head attention never mixes heads — bit-identical. The only
arithmetic that differs from tp=1 is the two psums per layer (split-K
partial sums), ~1 ulp in f32; greedy decode over a well-separated argmax is
token-exact.
"""
from __future__ import annotations

import inspect
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn.transformer import PagedDecoder
from ..parallel import mesh as mesh_lib

# The pool's (L, N, H_kv / p, bs, p * Dh) arrays split on axis 2, the page
# rows (whole groups of p heads). Used as a pytree prefix, so an int8 pool's
# QuantPages (data + scale sidecar, both rank 5 with heads on axis 2, p = 1)
# shard as one unit — scales travel with their heads.
PAGE_SPEC = P(None, None, "model", None, None)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _permute_qkv(w, num_heads: int, num_kv_heads: int, head_dim: int,
                 tp: int):
    """Reorder fused-qkv columns ``[q | k | v]`` -> per-shard groups
    ``[q_s | k_s | v_s]`` so a flat column chunking is head-aligned."""
    d, kv_d = num_heads * head_dim, num_kv_heads * head_dim
    w = np.asarray(w)
    lead = w.shape[:-1]
    q, k, v = np.split(w, [d, d + kv_d], axis=-1)
    q = q.reshape(lead + (tp, d // tp))
    k = k.reshape(lead + (tp, kv_d // tp))
    v = v.reshape(lead + (tp, kv_d // tp))
    return np.concatenate([q, k, v], axis=-1).reshape(lead + (-1,))


def _spec_for(path, leaf) -> P:
    """Partition spec for one param leaf, keyed on its tree path."""
    name = _path_str(path)
    if name.endswith("attn/qkv_kernel"):
        return P(None, "model")
    if name.endswith("attn/qkv_bias"):
        return P("model")
    if name.endswith("attn/out_kernel"):
        return P("model", None)
    if name.endswith("fc/kernel"):
        return P(None, "model")
    if name.endswith("fc/bias"):
        return P("model")
    if name.endswith("proj/kernel"):
        return P("model", None)
    return P()


class TPContext:
    """Everything the engine needs to run its step bodies over a TP mesh:
    the mesh, the sharded params, page/replicated shardings, the TP model
    adapter, and ``jit_step`` — the drop-in replacement for the engine's
    ``jax.jit(fn, donate_argnums=...)`` builder calls."""

    def __init__(self, model, params, tp: int, *,
                 devices: Optional[Sequence[Any]] = None, tracer=None):
        devices = list(devices) if devices is not None else jax.devices()
        tp = int(tp)
        if tp < 2:
            raise ValueError(f"TPContext needs tp >= 2, got {tp}")
        if tp > len(devices):
            raise ValueError(
                f"tp={tp} needs {tp} devices but only {len(devices)} are "
                "visible — on CPU hosts raise "
                "--xla_force_host_platform_device_count")
        if model.num_heads % tp:
            raise ValueError(
                f"num_heads={model.num_heads} not divisible by tp={tp}")
        if model.num_kv_heads % tp:
            raise ValueError(
                f"num_kv_heads={model.num_kv_heads} not divisible by "
                f"tp={tp} — each shard must own whole KV heads (same "
                "H_kv-divisibility constraint as Ulysses)")
        self.tp = tp
        self.base_model = model
        self.model = TPModel(model, tp)
        self.mesh = mesh_lib.make_mesh(model=tp, devices=devices[:tp])
        self.page_spec = PAGE_SPEC
        self.page_sharding = NamedSharding(self.mesh, PAGE_SPEC)
        self.replicated = NamedSharding(self.mesh, P())
        self.tracer = tracer  # set by the engine once its tracer exists
        # two collectives per layer: attn out-proj psum + MLP proj psum
        self.n_allreduce = 2 * model.num_layers
        self.param_specs = jax.tree_util.tree_map_with_path(
            _spec_for, params)
        self.params = self._shard_params(params)

    # -- params ---------------------------------------------------------------

    def _shard_params(self, params):
        m = self.base_model
        head_dim = m.d_model // m.num_heads

        def place(path, leaf):
            spec = _spec_for(path, leaf)
            name = _path_str(path)
            if name.endswith("attn/qkv_kernel") or \
                    name.endswith("attn/qkv_bias"):
                leaf = _permute_qkv(leaf, m.num_heads, m.num_kv_heads,
                                    head_dim, self.tp)
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(place, params)

    # -- step dispatch --------------------------------------------------------

    def jit_step(self, fn, *, donate_argnums=()):
        """Wrap a program body in shard_map over the TP mesh + jit.

        What an argument IS is read off the body's parameter names, the one
        convention every program of the engine follows: ``params`` takes
        the per-leaf specs, ``cache`` (the pool's arrays as one pytree) the
        page spec as a prefix, sharded head-wise, everything else is
        replicated; the body returns ``(sampled, cache, counts)``, the
        cache sharded like the one it was given and the small results on
        either side of it replicated.
        ``donate_argnums`` passes through to jit, so each shard's page
        buffers are donated and re-adopted exactly as in the single-chip
        step."""
        spec_of = {"params": self.param_specs, "cache": self.page_spec}
        body = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=tuple(spec_of.get(name, P())
                           for name in inspect.signature(fn).parameters),
            out_specs=(P(), self.page_spec, P()), check_vma=False)
        jitted = jax.jit(body, donate_argnums=donate_argnums)
        ctx = self

        def dispatch(*args):
            tracer = ctx.tracer
            if tracer is not None and getattr(tracer, "enabled", True):
                with tracer.span("serve.allreduce", tp=ctx.tp,
                                 count=ctx.n_allreduce):
                    return jitted(*args)
            return jitted(*args)

        return dispatch

    def put_replicated(self, x):
        """Host value -> replicated device array on the mesh (the TP form of
        the engine's ``_put``; committed single-device arrays can't mix with
        mesh-placed arrays in one jit call)."""
        return jax.device_put(x, self.replicated)


class TPModel(PagedDecoder):
    """Head-sharded adapter around a GPT2-family model.

    Presents the SAME interface and GLOBAL dimensions as the base model (the
    engine's host-side math — head_dim, pool sizing, batch packing — reads
    them unchanged) but its apply methods expect to run INSIDE shard_map
    with locally-sharded params/pages, using per-shard head counts for the
    attention split and psums to rebuild the residual stream. The paged
    step is ``PagedDecoder``'s own loop, over this adapter's blocks."""

    def __init__(self, base, tp: int):
        self.base = base
        self.tp = int(tp)
        self.vocab_size = base.vocab_size
        self.max_len = base.max_len
        self.num_layers = base.num_layers
        self.d_model = base.d_model
        self.num_heads = base.num_heads
        self.num_kv_heads = base.num_kv_heads
        self.moe_experts = getattr(base, "moe_experts", 0)
        self.kv_cache_dtype = getattr(base, "kv_cache_dtype", None)
        self.policy = base.policy
        self.backend = getattr(base, "backend", "xla")
        self.blocks = [TPBlock(b, tp) for b in base.blocks]

    # PagedDecoder's hooks: everything outside the blocks runs replicated
    # through the base model

    def _embed(self, params, toks, offsets):
        return self.base._embed(params, toks, offsets)

    def _ln_f(self, params, x):
        return self.base._ln_f(params, x)

    def _head(self, params, x):
        return self.base._head(params, x)


class TPBlock:
    """GPTBlock adapter: replicated layer norms + head-sharded attention +
    column/row-sharded MLP with one psum after the down-projection."""

    def __init__(self, base, tp: int):
        if getattr(base, "moe", None) is not None:
            raise ValueError("tensor-parallel serving does not support MoE "
                             "blocks (gate moe_experts off under tp>1)")
        self.base = base
        self.tp = int(tp)
        self.ln1 = base.ln1
        self.ln2 = base.ln2
        self.attn = TPAttention(base.attn, tp)
        self.mlp_ratio = base.mlp_ratio
        self.activation = base.activation

    def _mlp(self, params, h):
        # Dense._apply twice, with the contraction split: fc's kernel/bias
        # are column-sharded (activation applies elementwise to local
        # columns — exact), proj's kernel is row-sharded so its qmatmul is a
        # split-K partial sum; psum in f32 BEFORE the bias/cast rebuilds the
        # replicated activations.
        from ..nn import activations
        from ..ops.pallas.quant_matmul import qmatmul

        policy = self.base.policy
        h = policy.cast_in(h)
        w = policy.cast_param(params["fc"]["kernel"])
        h = qmatmul(h, w)
        h = h + params["fc"]["bias"].astype(jnp.float32)
        h = activations.get(self.activation)(h)
        h = policy.cast_out(h)
        h = policy.cast_in(h)
        w = policy.cast_param(params["proj"]["kernel"])
        h = qmatmul(h, w)
        h = jax.lax.psum(h, "model")
        h = h + params["proj"]["bias"].astype(jnp.float32)
        return policy.cast_out(h)

    def apply_paged(self, params, x, pages_k, pages_v, block_tables, offsets,
                    layer, q_lens=None):
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, pages_k, pages_v = self.attn.apply_paged(
            {"params": params["attn"]}, h, pages_k, pages_v, block_tables,
            offsets, layer=layer, q_lens=q_lens)
        x = x + h
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        h = self._mlp(params, h)
        return x + h, pages_k, pages_v


class TPAttention:
    """MultiHeadAttention adapter with local head counts.

    The base module derives head_dim and the q/k/v split widths from the
    FULL model dim and its own head counts, which is wrong once the fused
    qkv output is a local shard — this adapter carries the local counts
    (``hl = H/tp`` query heads, ``kl = H_kv/tp`` kv heads) explicitly and
    otherwise mirrors the base cast chain operation-for-operation, plus the
    one psum after the out-projection (before the replicated bias)."""

    def __init__(self, base, tp: int):
        self.base = base
        self.tp = int(tp)
        self.hl = base.num_heads // tp
        self.kl = base.num_kv_heads // tp

    # base._split_heads reads d from x and h from the module — supply the
    # local head count and per-head dim explicitly instead
    @staticmethod
    def _split_heads(x, h):
        n, s, d = x.shape
        return x.reshape(n, s, h, d // h).transpose(0, 2, 1, 3)

    @staticmethod
    def _merge_heads(x):
        n, h, s, dh = x.shape
        return x.transpose(0, 2, 1, 3).reshape(n, s, h * dh)

    def _project_qkv(self, params, x):
        from ..ops.pallas.quant_matmul import qmatmul

        base = self.base
        policy = base.policy
        dh = x.shape[-1] // base.num_heads  # x keeps the GLOBAL model dim
        x = policy.cast_in(x)
        w = policy.cast_param(params["qkv_kernel"])  # local: (D, (hl+2kl)*dh)
        qkv = qmatmul(x, w).astype(x.dtype)
        if base.use_bias:
            qkv = qkv + params["qkv_bias"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [self.hl * dh, (self.hl + self.kl) * dh],
                            axis=-1)
        return (self._split_heads(q, self.hl), self._split_heads(k, self.kl),
                self._split_heads(v, self.kl))

    def _project_out(self, params, attn):
        from ..ops.pallas.quant_matmul import qmatmul

        policy = self.base.policy
        y = self._merge_heads(attn)                  # (B, S, hl*dh) local
        w = policy.cast_param(params["out_kernel"])  # local: (hl*dh, D) rows
        y0 = qmatmul(y, w)                           # f32 partial sum
        y = jax.lax.psum(y0, "model").astype(y.dtype)
        if self.base.use_bias:
            y = y + params["out_bias"].astype(y.dtype)
        # dropout is decode-only here (train=False) — a no-op, omitted
        return policy.cast_out(y)

    def apply_paged(self, variables, x, pages_k, pages_v, block_tables,
                    offsets, layer=0, q_lens=None):
        from ..nn.attention import apply_rope
        from ..ops.pallas import paged_attention as pa

        base = self.base
        params = variables["params"]
        q, k_new, v_new = self._project_qkv(params, x)
        if base.rope_theta:
            q = apply_rope(q, offsets, base.rope_theta)
            k_new = apply_rope(k_new, offsets, base.rope_theta)
        quant_pool = isinstance(pages_k, pa.QuantPages)
        if q_lens is None and x.shape[1] == 1:
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
                                     layer=layer)
            y = self._project_out(params, out[:, :, None, :])
            return y, pages_k, pages_v
        if q_lens is None:
            raise ValueError("apply_paged with Q > 1 requires q_lens")
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
                                 q_lens=q_lens, layer=layer)
        y = self._project_out(params, out.transpose(0, 2, 1, 3))
        return y, pages_k, pages_v
