"""Sequence-parallel serving: shard one request's KV blocks over a context
mesh.

Long-context serving (docs/serving.md, "Sequence-parallel long-context
serving"): the paged KV pool is range-partitioned on the BLOCK axis — shard
s of sp owns global block ids ``[s*N_local, (s+1)*N_local)`` — so a single
sequence's pages spread round-robin over the mesh's ``seq`` axis and the
aggregate pool is sp× one chip's. Every shard holds the full (replicated)
params and runs the full model; the ONLY sharded state is the pages, and
the only collective is one online-softmax merge per layer
(``ops.softmax_merge.merge_psum``): each shard sweeps the ~1/sp of the
sequence it owns with the ragged paged-attention kernel (emitting per-row
``(m, l)`` stats), and the partials combine into exactly the full-row
softmax.

Contrast with tensor parallelism (serving/tp.py): TP shards HEADS — every
shard still holds every block, so the pool (and max context) does not grow;
SP shards BLOCKS — per-chip KV memory drops sp×, which is the long-context
axis. The two compose conceptually but are mutually exclusive in this
engine (``sp``×``tp`` is rejected at construction).

Layout (shard s of sp):

    every param leaf                                     -> P() (replicated)
    pages_k / pages_v  (L, N, H_kv/p, bs, p*Dh) axis 1   -> P(None, "seq")
    block tables       (sp, B, nb) stacked per-shard     -> P("seq")
    tokens / offsets / kv_lens / sampling params         -> P() (replicated)

Per-shard block tables (``step_build.shard_tables``) carry LOCAL row ids
for owned positions and ``-1`` holes elsewhere: the kernel skips ``-1``
blocks, the scatters redirect them to the shard's scratch page, and
positions stay GLOBAL everywhere, so causal masking and RoPE are untouched.

Exactness contract (tested token-exact in tests/test_sp_serving.py): every
matmul is replicated — bit-identical to sp=1. The only arithmetic that
differs is the reassociated softmax: per-shard online softmax + one merge
psum per layer, the same reassociation flash attention itself performs
block-to-block, ~1 ulp in f32; greedy decode over a well-separated argmax
is token-exact.
"""
from __future__ import annotations

import inspect
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn.transformer import PagedDecoder
from ..parallel import mesh as mesh_lib
from . import step_build

# The pool's (L, N, H_kv / p, bs, p * Dh) arrays split on the BLOCK axis. Used
# as a pytree prefix, so an int8 pool's QuantPages (data + scale sidecar, both
# rank 5 with blocks on axis 1) shard as one unit — scales travel with
# their pages.
PAGE_SPEC = P(None, "seq", None, None, None)

# Stacked per-shard block tables: leading axis one entry per shard. A
# partial spec (trailing dims replicated) so the SAME spec covers the
# (sp, B, nb) step tables and the (sp, 1, k) block-id arguments of the
# COW/adopt steps.
TABLE_SPEC = P("seq")


class SPContext:
    """Everything the engine needs to run its step bodies over a context
    mesh: the mesh, replicated params, page/table shardings, the SP model
    adapter, and ``jit_step`` — the drop-in replacement for the engine's
    ``jax.jit(fn, donate_argnums=...)`` builder calls (mirrors
    serving/tp.TPContext)."""

    def __init__(self, model, params, sp: int, *,
                 devices: Optional[Sequence[Any]] = None, tracer=None):
        devices = list(devices) if devices is not None else jax.devices()
        sp = int(sp)
        if sp < 2:
            raise ValueError(f"SPContext needs sp >= 2, got {sp}")
        if sp > len(devices):
            raise ValueError(
                f"sp={sp} needs {sp} devices but only {len(devices)} are "
                "visible — on CPU hosts raise "
                "--xla_force_host_platform_device_count")
        self.sp = sp
        self.base_model = model
        self.model = SPModel(model, sp)
        self.mesh = mesh_lib.make_mesh(seq=sp, devices=devices[:sp])
        self.page_spec = PAGE_SPEC
        self.page_sharding = NamedSharding(self.mesh, PAGE_SPEC)
        self.table_sharding = NamedSharding(self.mesh, TABLE_SPEC)
        self.replicated = NamedSharding(self.mesh, P())
        self.tracer = tracer  # set by the engine once its tracer exists
        # one collective per layer: the online-softmax merge psum
        self.n_combine = model.num_layers
        # params are fully replicated — every shard runs the whole model;
        # only the pages (and the per-shard tables) are sharded
        self.params = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, self.replicated), params)

    # -- step dispatch --------------------------------------------------------

    def jit_step(self, fn, *, donate_argnums=()):
        """Wrap a program body in shard_map over the context mesh + jit.

        What an argument IS is read off the body's parameter names, the one
        convention every program of the engine follows: ``cache`` (the
        pool's arrays as one pytree) takes the page spec as a prefix,
        sharded block-wise; ``tables`` is the stacked per-shard block table
        or block id (the host stages a (sp, ...) array via ``put_tables``
        and each shard sees its own slice: the leading unit axis is
        squeezed before ``fn`` runs, so the step-body code is IDENTICAL to
        the single-chip program); everything else, the params too, is
        replicated. The body returns ``(sampled, cache, counts)``, the
        cache sharded like the one it was given and the small results on
        either side of it replicated.
        ``donate_argnums`` passes through to jit, so each shard's page
        buffers are donated and re-adopted exactly as in the single-chip
        step."""
        names = list(inspect.signature(fn).parameters)
        spec_of = {"cache": self.page_spec, "tables": TABLE_SPEC}
        inner = fn
        if "tables" in names:
            t_idx = names.index("tables")

            def inner(*args):
                args = list(args)
                args[t_idx] = args[t_idx][0]  # (1, B, nb) -> (B, nb)
                return fn(*args)

        body = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=tuple(spec_of.get(name, P()) for name in names),
            out_specs=(P(), self.page_spec, P()), check_vma=False)
        jitted = jax.jit(body, donate_argnums=donate_argnums)
        ctx = self

        def dispatch(*args):
            tracer = ctx.tracer
            if tracer is not None and getattr(tracer, "enabled", True):
                with tracer.span("serve.spmerge", sp=ctx.sp,
                                 count=ctx.n_combine):
                    return jitted(*args)
            return jitted(*args)

        return dispatch

    def put_replicated(self, x):
        """Host value -> replicated device array on the mesh (the SP form of
        the engine's ``_put``; committed single-device arrays can't mix with
        mesh-placed arrays in one jit call)."""
        return jax.device_put(x, self.replicated)

    def put_tables(self, tables: np.ndarray, blocks_per_shard: int):
        """Stage GLOBAL block tables (any rank — step tables, COW/adopt
        block-id pairs) as the stacked per-shard
        (sp, ...) device array ``jit_step`` hands a body's ``tables``:
        shard s's slice holds LOCAL row ids for the positions it owns and
        ``-1`` holes for everyone else's."""
        stacked = step_build.shard_tables(np.asarray(tables, np.int32),
                                          self.sp, blocks_per_shard)
        return jax.device_put(stacked, self.table_sharding)


class SPModel(PagedDecoder):
    """Block-sharded adapter around a GPT2-family model.

    Presents the SAME interface and dimensions as the base model — every
    parameter and every matmul is replicated, so most methods delegate
    verbatim. Only the paged-attention call differs: each shard sweeps its
    own pages and the partials merge across the mesh (``SPAttention``),
    inside ``PagedDecoder``'s own loop over this adapter's blocks."""

    def __init__(self, base, sp: int):
        self.base = base
        self.sp = int(sp)
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
        self.blocks = [SPBlock(b, sp) for b in base.blocks]

    # PagedDecoder's hooks: everything outside the blocks is the base model's

    def _embed(self, params, toks, offsets):
        return self.base._embed(params, toks, offsets)

    def _ln_f(self, params, x):
        return self.base._ln_f(params, x)

    def _head(self, params, x):
        return self.base._head(params, x)


class SPBlock:
    """GPTBlock adapter: everything replicated except the attention sweep."""

    def __init__(self, base, sp: int):
        if getattr(base, "moe", None) is not None:
            raise ValueError("sequence-parallel serving does not support MoE "
                             "blocks (gate moe_experts off under sp>1)")
        self.base = base
        self.sp = int(sp)
        self.attn = SPAttention(base.attn, sp)

    def apply_paged(self, params, x, pages_k, pages_v, block_tables, offsets,
                    layer, q_lens=None):
        base = self.base
        h, _ = base.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, pages_k, pages_v = self.attn.apply_paged(
            {"params": params["attn"]}, h, pages_k, pages_v, block_tables,
            offsets, layer=layer, q_lens=q_lens)
        x = x + h
        h, _ = base.ln2.apply({"params": params["ln2"], "state": {}}, x)
        h, _ = base._mlp(params, h, False, None)
        return x + h, pages_k, pages_v


class SPAttention:
    """MultiHeadAttention adapter for the per-shard page sweep.

    Projections, RoPE and head math run replicated through the base module
    (full head counts, full model dim — bit-identical to sp=1). The shard's
    LOCAL block table steers the KV scatter (``-1`` holes land in the
    shard's scratch page) and the ragged kernel sweeps only owned pages,
    emitting per-row ``(m, l)`` stats; ``softmax_merge.merge_psum`` over the
    ``seq`` axis then rebuilds exactly the full-sequence softmax before the
    replicated out-projection."""

    def __init__(self, base, sp: int):
        self.base = base
        self.sp = int(sp)

    def apply_paged(self, variables, x, pages_k, pages_v, block_tables,
                    offsets, layer=0, q_lens=None):
        from ..nn.attention import apply_rope
        from ..ops import softmax_merge
        from ..ops.pallas import paged_attention as pa

        base = self.base
        if base.kv_cache_dtype == "int8":
            raise NotImplementedError(
                "paged decode with int8 KV pages is future work — pool pages "
                "are compute-dtype (see docs/serving.md limits)")
        params = variables["params"]
        q, k_new, v_new = base._project_qkv(params, x)
        if base.rope_theta:
            # positions are GLOBAL on every shard — rotation is untouched
            q = apply_rope(q, offsets, base.rope_theta)
            k_new = apply_rope(k_new, offsets, base.rope_theta)
        quant_pool = isinstance(pages_k, pa.QuantPages)
        if q_lens is None and x.shape[1] == 1:
            rows_k, rows_v = k_new[:, :, 0], v_new[:, :, 0]
            if not quant_pool:
                rows_k = rows_k.astype(pages_k.dtype)
                rows_v = rows_v.astype(pages_v.dtype)
            # -1 holes (positions another shard owns) redirect to this
            # shard's scratch page inside the scatter helpers
            pages_k = pa.scatter_kv_rows(pages_k, block_tables, offsets,
                                         rows_k, layer=layer)
            pages_v = pa.scatter_kv_rows(pages_v, block_tables, offsets,
                                         rows_v, layer=layer)
            out, m, l = pa.paged_attention(  # noqa: E741
                q[:, :, 0], pages_k, pages_v, block_tables,
                kv_lens=offsets + 1, layer=layer, return_stats=True)
            out = softmax_merge.merge_psum(out, m, l, "seq")
            y = base._project_out(params, out[:, :, None, :], False, None)
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
        out, m, l = pa.paged_attention(  # noqa: E741
            q.transpose(0, 2, 1, 3), pages_k, pages_v, block_tables,
            kv_lens=offsets + q_lens, q_lens=q_lens, layer=layer,
            return_stats=True)
        out = softmax_merge.merge_psum(out, m, l, "seq")
        y = base._project_out(params, out.transpose(0, 2, 1, 3), False, None)
        return y, pages_k, pages_v
