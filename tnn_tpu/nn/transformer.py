"""Transformer blocks: GPT block (pre-LN) and encoder (ViT) block.

Parity: reference ``gpt_block`` builder (include/nn/layer_builder.hpp:531-570):
ResidualBlock(LayerNorm -> AttentionBlock -> Dropout) then
ResidualBlock(LayerNorm -> Dense(4E) GELU -> Dense(E) -> Dropout); ``flash_gpt_block``
(:575) maps to backend="pallas". ViT encoder block shares the structure.

Implemented as a dedicated Module (not the generic containers) so the KV-cache decode
path (``apply_cached``) can thread per-layer caches — the functional analog of the
reference's per-microbatch activation caches (include/nn/layer.hpp:113-114).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import rng as rnglib
from ..core.module import Module, register_module
from . import initializers
from .attention import MultiHeadAttention
from .layers import Dense, Dropout
from .norms import LayerNorm


class PagedDecoder:
    """The serving step of a decoder-only LM against the paged KV pool, for
    any model made of ``self.blocks`` whose blocks have ``apply_paged``:
    ``_embed(params, toks, offsets)``, the blocks, ``_ln_f``, ``_head``. One
    loop for every family (GPT-2's block, the Llama block and its EvaByte
    configuration), so the engine's normal path takes each of them."""

    def apply_paged(self, params, toks, pages_k, pages_v, block_tables,
                    offsets, q_lens=None, state=None, head_at=None):
        """Ragged multi-token step against the paged KV pool.

        toks is (B, Q) with row b carrying ``q_lens[b]`` live new tokens
        starting at position ``offsets[b]`` (the rest padding: their KV lands
        in the pool's scratch page, their logits are garbage); pages_k /
        pages_v the pool's (L, N, H_kv / p, bs, p * Dh) arrays, L ==
        ``cache_layers``;
        block_tables (B, nb) page ids. Every layer writes its new K/V rows
        into their pages and attends over the tables (the block's
        ``apply_paged``): no contiguous cache is ever assembled. Returns
        (logits (B, Q, V), pages_k, pages_v); the caller reads row b's
        next-token logits at q position ``q_lens[b] - 1``, and donates the
        pages through jit for in-place pool updates. ``q_lens`` None is the
        decode form (Q == 1).

        ``state``: the pool's state slots, for a model some of whose layers
        keep a state updated in place and no pages (``_paged_layers`` gives
        such a layer its ``slots``; its block's ``apply_state`` takes the
        state and returns it). It is then a fourth result: the pages and
        the state are what the engine donates as its one ``cache``.
        ``head_at`` (B,): only that position of each row goes through
        the head, logits (B, 1, V): a wide step of a large vocabulary then
        holds no (B, Q, V) cube (the engine's mixed step passes each row's
        last live position, for every model)."""
        x = self._embed(params, toks, offsets)
        where = self._paged_layers(pages_k, block_tables)
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                if "slots" in where[i]:
                    x, state = block.apply_state(
                        params[f"h{i}"], x, state, offsets=offsets,
                        q_lens=q_lens, **where[i])
                    continue
                x, pages_k, pages_v = block.apply_paged(
                    params[f"h{i}"], x, pages_k, pages_v, offsets=offsets,
                    q_lens=q_lens, **where[i])
        if head_at is not None:
            x = jnp.take_along_axis(x, head_at[:, None, None], axis=1)
        logits = self._head(params, self._ln_f(params, x))
        if state is None:
            return logits, pages_k, pages_v
        return logits, pages_k, pages_v, state

    @property
    def cache_layers(self) -> int:
        """The layers of the pool this model writes: one a block, or as many
        as a block of several attention sublayers says (its
        ``cache_layers``). The engine sizes the pool by this, not by
        ``num_layers``."""
        return sum(getattr(b, "cache_layers", 1) for b in self.blocks)

    def _paged_layers(self, pages_k, block_tables):
        """Per block, the keywords of its ``apply_paged`` that say where its
        pages are: the step's one table and the block's own layer of the
        pool (a block of several attention sublayers: its layers, as a
        tuple), unless the model keeps its layers' pages otherwise."""
        out, at = [], 0
        for block in self.blocks:
            n = getattr(block, "cache_layers", 1)
            out.append(dict(block_tables=block_tables,
                            layer=at if n == 1 else tuple(range(at, at + n))))
            at += n
        return out

    def apply_decode_paged(self, params, toks, pages_k, pages_v, block_tables,
                           offsets, state=None):
        """One decode step: toks (B,) this step's token per row, offsets (B,)
        each row's position (kv length before this token). Returns
        (last-position logits (B, V), pages_k, pages_v), and the ``state``
        it was given."""
        logits, *rest = self.apply_paged(
            params, toks[:, None], pages_k, pages_v, block_tables, offsets,
            state=state)
        return (logits[:, -1], *rest)


@register_module("gpt_block")
class GPTBlock(Module):
    """Pre-LN transformer decoder block (parity: gpt_block, layer_builder.hpp:531)."""

    def __init__(self, num_heads: int, mlp_ratio: int = 4, dropout: float = 0.0,
                 causal: bool = True, backend: str = "xla", activation: str = "gelu",
                 moe_experts: int = 0, moe_top_k: int = 2, num_kv_heads=None,
                 kv_cache_dtype=None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        self.kv_cache_dtype = kv_cache_dtype
        self.mlp_ratio = int(mlp_ratio)
        self.dropout = float(dropout)
        self.causal = bool(causal)
        self.backend = backend
        self.activation = activation
        self.moe_experts = int(moe_experts)
        self.moe_top_k = int(moe_top_k)
        p = self.policy
        self.ln1 = LayerNorm(policy=p)
        self.attn = MultiHeadAttention(num_heads, causal=causal, dropout=dropout,
                                       backend=backend,
                                       num_kv_heads=self.num_kv_heads,
                                       kv_cache_dtype=kv_cache_dtype, policy=p)
        self.ln2 = LayerNorm(policy=p)
        self.drop = Dropout(dropout, policy=p)
        self.moe = None
        if self.moe_experts > 0:  # MoE FFN replaces the dense MLP
            from .moe import MoE

            self.moe = MoE(self.moe_experts, top_k=self.moe_top_k,
                           activation=activation,
                           hidden_ratio=self.mlp_ratio,  # honor the FFN width
                           policy=p)

    def _mlp_layers(self, d):
        p = self.policy
        return (Dense(self.mlp_ratio * d, activation=self.activation, policy=p),
                Dense(d, policy=p))

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        params = {
            "ln1": self.ln1.init(k1, input_shape)["params"],
            "attn": self.attn.init(k2, input_shape)["params"],
            "ln2": self.ln2.init(k3, input_shape)["params"],
        }
        state = {}
        if self.moe is not None:
            mv = self.moe.init(k4, input_shape)
            params["moe"] = mv["params"]
            state = mv["state"]  # {"aux_loss": 0} — structure must be stable
        else:
            fc, proj = self._mlp_layers(d)
            mlp_shape = tuple(input_shape[:-1]) + (self.mlp_ratio * d,)
            params["fc"] = fc.init(k4, input_shape)["params"]
            params["proj"] = proj.init(k5, mlp_shape)["params"]
        return params, state

    def _mlp(self, params, h, train, rng):
        if self.moe is not None:
            out, moe_state = self.moe.apply(
                {"params": params["moe"], "state": {}}, h, train=train, rng=rng)
            return out, moe_state
        d = h.shape[-1]
        fc, proj = self._mlp_layers(d)
        h, _ = fc.apply({"params": params["fc"], "state": {}}, h, train=train)
        h, _ = proj.apply({"params": params["proj"], "state": {}}, h, train=train)
        return h, {}

    def _apply(self, params, state, x, *, train, rng):
        # dense blocks keep their original 3-key split so pre-MoE seeded runs
        # reproduce exactly; only MoE blocks draw a 4th key for the router
        if self.moe is not None:
            k1, k2, k3, k4 = rnglib.split_for(rng, 4)
        else:
            k1, k2, k3 = rnglib.split_for(rng, 3)
            k4 = None
        h = self._ln1(params, x)
        h, _ = self.attn.apply({"params": params["attn"], "state": {}}, h,
                               train=train, rng=k1)
        with jax.named_scope("attn_out"):
            h, _ = self.drop.apply({}, h, train=train, rng=k2)
            x = x + h
        with jax.named_scope("mlp"):
            h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
            h, new_state = self._mlp(params, h, train, k4)
            h, _ = self.drop.apply({}, h, train=train, rng=k3)
            return x + h, new_state

    # -- cached decode --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        return self.attn.init_cache(batch, max_len, d_model)

    # Layer scopes of the device profile (docs/observability.md): the block's
    # first norm counts as ``attn_qkv``, each residual add as the sublayer it
    # closes (``attn_out`` / ``mlp``); the attention module names the rest.

    @jax.named_scope("attn_qkv")
    def _ln1(self, params, x):
        return self.ln1.apply({"params": params["ln1"], "state": {}}, x)[0]

    @jax.named_scope("mlp")
    def _mlp_residual(self, params, x):
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        h, _ = self._mlp(params, h, False, None)
        return x + h

    def apply_cached(self, params, x, cache, offset):
        h = self._ln1(params, x)
        h, new_cache = self.attn.apply_cached({"params": params["attn"]}, h, cache, offset)
        with jax.named_scope("attn_out"):
            x = x + h
        return self._mlp_residual(params, x), new_cache

    def apply_paged(self, params, x, pages_k, pages_v, block_tables, offsets,
                    layer, q_lens=None):
        """apply_cached against the paged KV pool instead of an assembled
        cache — see MultiHeadAttention.apply_paged for the contract."""
        h = self._ln1(params, x)
        h, pages_k, pages_v = self.attn.apply_paged(
            {"params": params["attn"]}, h, pages_k, pages_v, block_tables,
            offsets, layer=layer, q_lens=q_lens)
        with jax.named_scope("attn_out"):
            x = x + h
        return self._mlp_residual(params, x), pages_k, pages_v

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads, "mlp_ratio": self.mlp_ratio,
               "dropout": self.dropout, "causal": self.causal,
               "backend": self.backend, "activation": self.activation}
        if self.num_kv_heads != self.num_heads:
            cfg["num_kv_heads"] = self.num_kv_heads
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        if self.moe_experts:
            cfg["moe_experts"] = self.moe_experts
            cfg["moe_top_k"] = self.moe_top_k
        return cfg


@register_module("encoder_block")
class EncoderBlock(GPTBlock):
    """Non-causal pre-LN encoder block (ViT). Same structure, causal=False default."""

    def __init__(self, num_heads: int, mlp_ratio: int = 4, dropout: float = 0.0,
                 backend: str = "xla", activation: str = "gelu", name=None, policy=None):
        super().__init__(num_heads, mlp_ratio=mlp_ratio, dropout=dropout, causal=False,
                         backend=backend, activation=activation, name=name, policy=policy)

    def _config(self):
        cfg = super()._config()
        cfg.pop("causal")
        return cfg
