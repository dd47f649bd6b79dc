"""Llama-family decoder-only LM: RoPE + RMSNorm + SwiGLU + GQA.

Beyond the reference (whose transformer story ends at GPT-2,
src/nn/example_models.cpp:384-504): the architecture modern open models
actually use, assembled from pieces this framework already has TPU-first —
rotary embeddings with absolute-position offsets through cached decode
(nn/attention.py apply_rope), zero-copy grouped-query attention in the flash
kernels, RMSNorm, and a gated SwiGLU MLP. ``models.gpt2.generate`` drives it
unchanged (duck-typed on init_cache/apply_cached/max_len).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import rng as rnglib
from ..core.module import Module, register_module
from ..nn.attention import (GatedAttention, LatentAttention,
                            MultiHeadAttention, state_mixer)
from ..nn.embedding import Embedding
from ..nn.layers import Dense
from ..nn.moe import ExpertShare
from ..nn.norms import RMSNorm
from ..nn.transformer import PagedDecoder


@register_module("llama_block")
class LlamaBlock(Module):
    """Pre-RMSNorm decoder block: x + attn(rms(x)); x + swiglu(rms(x)).

    SwiGLU MLP: down( silu(gate(h)) * up(h) ) — three bias-free projections
    with an explicit ``mlp_hidden`` width (Llama uses ~8/3 * d rounded, not
    the GPT 4x)."""

    def __init__(self, num_heads: int, mlp_hidden: int,
                 num_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = 10000.0, backend: str = "xla",
                 kv_cache_dtype: Optional[str] = None,
                 norm_eps: float = 1e-6, norm_unit_offset: bool = False,
                 residual_f32: bool = False, window: Optional[int] = None,
                 chunk: Optional[int] = None, latent: Optional[dict] = None,
                 experts: Optional[dict] = None,
                 gated: Optional[dict] = None, sandwich: bool = False,
                 linear: Optional[dict] = None,
                 scales: Optional[dict] = None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_heads = int(num_heads)
        self.mlp_hidden = int(mlp_hidden)
        # ``linear``: the keywords of a state mixer (``nn.attention.
        # state_mixer``: Gated DeltaNet, or the one ``mixer`` names): THIS
        # layer keeps a state updated in place and no page of the pool
        self.linear = dict(linear) if linear else None
        # ``scales``: the model's published multipliers (``Llama``); a block
        # reads ``residual`` (each sublayer's output times it before it is
        # added) and ``attention`` (the softmax's scale)
        self.scales = dict(scales) if scales else None
        # the block is GIVEN its attention (heads: K/V of every position;
        # eva: ``window`` and ``chunk``; latent: ``latent``, the keywords of
        # ``nn.attention.LatentAttention``; gated: ``gated``, those of
        # ``nn.attention.GatedAttention``, THIS layer's: sliding with its
        # window and rotary base, or global with neither) and its
        # feed-forward (gated, or ``experts``: the keywords of
        # ``nn.moe.ExpertShare``)
        self.latent = dict(latent) if latent else None
        self.experts = dict(experts) if experts else None
        self.gated = dict(gated) if gated else None
        # ``sandwich``: a norm on each sublayer's OUTPUT too, before it is
        # added (four norms a block)
        self.sandwich = bool(sandwich)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        # None: no rotation at all (positions reach the layer through the
        # state layers under it)
        self.rope_theta = float(rope_theta) if rope_theta else None
        self.backend = backend
        self.kv_cache_dtype = kv_cache_dtype
        self.norm_eps = float(norm_eps)
        self.norm_unit_offset = bool(norm_unit_offset)
        # the residual sum kept in float32 while the sublayers compute in
        # the policy's dtype (EvaByte's ``fp32_skip_add``)
        self.residual_f32 = bool(residual_f32)
        self.window, self.chunk = window, chunk
        p = self.policy
        norm = dict(eps=self.norm_eps, unit_offset=self.norm_unit_offset,
                    policy=p)
        self.ln1 = RMSNorm(**norm)
        if self.linear:
            self.attn = state_mixer(self.linear, norm_eps=self.norm_eps,
                                    policy=p)
        elif self.latent:
            self.attn = LatentAttention(num_heads, norm_eps=self.norm_eps,
                                        backend=backend, policy=p,
                                        **self.latent)
        elif self.gated:
            self.attn = GatedAttention(num_heads, self.num_kv_heads,
                                       norm_eps=self.norm_eps,
                                       backend=backend, policy=p,
                                       **self.gated)
        else:
            self.attn = MultiHeadAttention(
                num_heads, causal=True, backend=backend,
                num_kv_heads=self.num_kv_heads, rope_theta=self.rope_theta,
                use_bias=False, kv_cache_dtype=kv_cache_dtype, window=window,
                chunk=chunk, scale=_scale(self, "attention"), policy=p)
        self.ln2 = RMSNorm(**norm)
        self.post = RMSNorm(**norm) if self.sandwich else None
        self.moe = ExpertShare(policy=p, **self.experts) \
            if self.experts else None
        self.gate = Dense(self.mlp_hidden, use_bias=False, policy=p)
        self.up = Dense(self.mlp_hidden, use_bias=False, policy=p)
        # the down projection needs the model dim, known only at init —
        # constructed per use like GPTBlock._mlp_layers

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        # the sandwich's two norms draw keys of their own; a block without
        # them splits as it always did (seeded initialisations stay put)
        keys = jax.random.split(rng, 8 if self.sandwich else 6)
        k1, k2, k3, k4, k5, k6 = keys[:6]
        down = Dense(d, use_bias=False, policy=self.policy)
        hidden_shape = tuple(input_shape[:-1]) + (self.mlp_hidden,)
        params = {
            "ln1": self.ln1.init(k1, input_shape)["params"],
            "attn": self.attn.init(k2, input_shape)["params"],
            "ln2": self.ln2.init(k3, input_shape)["params"],
        }
        if self.sandwich:
            for name, key in (("ln1_post", keys[6]), ("ln2_post", keys[7])):
                params[name] = self.post.init(key, input_shape)["params"]
        if self.moe is not None:
            params["moe"] = self.moe.init(k4, input_shape)["params"]
        else:
            params.update(
                gate=self.gate.init(k4, input_shape)["params"],
                up=self.up.init(k5, input_shape)["params"],
                down=down.init(k6, hidden_shape)["params"])
        return params, {}

    @jax.named_scope("mlp")
    def _swiglu(self, params, h, train):
        d = h.shape[-1]
        g, _ = self.gate.apply({"params": params["gate"], "state": {}}, h,
                               train=train)
        u, _ = self.up.apply({"params": params["up"], "state": {}}, h,
                             train=train)
        down = Dense(d, use_bias=False, policy=self.policy)
        out, _ = down.apply({"params": params["down"], "state": {}},
                            jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype)
                            * u, train=train)
        return out

    # Layer scopes of the device profile as GPTBlock names them
    # (docs/observability.md): the first norm counts as ``attn_qkv``, each
    # residual add as the sublayer it closes.

    @jax.named_scope("attn_qkv")
    def _ln1(self, params, x):
        return self.ln1.apply({"params": params["ln1"], "state": {}}, x)[0]

    def _add(self, x, h, post=None):
        """x + h, ``h`` first through the sandwich's norm ``post`` of the
        sublayer it closes (in the residual's dtype)."""
        h = h.astype(x.dtype)
        if self.sandwich:
            h = self.post.apply({"params": post, "state": {}}, h)[0]
        if _scale(self, "residual"):
            h = h * jnp.asarray(_scale(self, "residual"), h.dtype)
        return x + h

    def _mlp_residual(self, params, x, train=False, live=None):
        if self.moe is not None:
            return self._experts_residual(params, x, live)
        with jax.named_scope("mlp"):
            h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
            return self._add(x, self._swiglu(params, h, train),
                             params.get("ln2_post"))

    def _experts_residual(self, params, x, live):
        """x + experts(rms(x)): the norm (on the float32 residual, so the
        router sees float32) counts as ``moe_route``; ``live`` (B, Q) marks
        a step's tokens that are no padding."""
        with jax.named_scope("moe_route"):
            h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        y, _ = self.moe.apply({"params": params["moe"], "state": {}}, h,
                              live=live)
        with jax.named_scope("moe_shared"):
            return self._add(x, y, params.get("ln2_post"))

    @jax.named_scope("attn_out")
    def _attn_residual(self, params, x, h):
        return self._add(x, h, params.get("ln1_post"))

    def _apply(self, params, state, x, *, train, rng):
        k1 = rnglib.split_for(rng, 1)[0]
        h, _ = self.attn.apply({"params": params["attn"], "state": {}},
                               self._ln1(params, x), train=train, rng=k1)
        return self._mlp_residual(
            params, self._attn_residual(params, x, h), train), state

    # -- cached decode --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        return self.attn.init_cache(batch, max_len, d_model)

    def apply_cached(self, params, x, cache, offset):
        h, new_cache = self.attn.apply_cached(
            {"params": params["attn"]}, self._ln1(params, x), cache, offset)
        return self._mlp_residual(
            params, self._attn_residual(params, x, h)), new_cache

    def apply_paged(self, params, x, pages_k, pages_v, block_tables, offsets,
                    layer, q_lens=None, **where):
        """apply_cached against the paged KV pool (see
        MultiHeadAttention.apply_paged for the contract). ``where``: what
        else says where THIS layer's pages are (a window layer's
        ``table_base``: ``GatedAttention.apply_paged``)."""
        h, pages_k, pages_v = self.attn.apply_paged(
            {"params": params["attn"]}, self._ln1(params, x), pages_k,
            pages_v, block_tables, offsets, layer=layer, q_lens=q_lens,
            **where)
        x = self._attn_residual(params, x, h)
        live = _live_tokens(x, block_tables, q_lens) \
            if self.moe is not None else None
        return self._mlp_residual(params, x, live=live), pages_k, pages_v

    @property
    def cache_layers(self) -> int:
        """Layers of the pool's pages the block writes: none where its
        attention keeps a state instead."""
        return 0 if self.linear else 1

    def apply_state(self, params, x, state, slots, offsets, state_layer,
                    q_lens=None):
        """``apply_paged`` for a block whose mixer keeps a state in the
        pool's state slots (``nn.attention._StateMixer.apply_state``,
        whichever mixer it is) and no pages: returns (x, state)."""
        h, state = self.attn.apply_state(
            {"params": params["attn"]}, self._ln1(params, x), state, slots,
            offsets, layer=state_layer, q_lens=q_lens)
        x = self._attn_residual(params, x, h)
        live = _live_tokens(x, slots[:, None], q_lens) \
            if self.moe is not None else None
        return self._mlp_residual(params, x, live=live), state

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads, "mlp_hidden": self.mlp_hidden,
               "rope_theta": self.rope_theta, "backend": self.backend}
        if self.num_kv_heads != self.num_heads:
            cfg["num_kv_heads"] = self.num_kv_heads
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        cfg.update(_block_options(self))
        return cfg


def _live_tokens(x, block_tables, q_lens):
    """(B, Q) bool: a step's tokens that are no padding, which take no
    expert: past a row's live tokens, or (the decode form) a row whose table
    is the scratch page."""
    if q_lens is not None:
        return jnp.arange(x.shape[1])[None, :] < q_lens[:, None]
    return block_tables[:, :1] > 0


@register_module("shortcut_block")
class ShortcutBlock(Module):
    """A block of TWO attention sublayers and two dense feed-forwards with
    ONE expert layer across them (shortcut-connected experts): the experts
    read the first sublayer's post-attention norm and their output is added
    at the END of the second, so that (on a deployment that divides the
    experts over chips) their exchange overlaps the first dense feed-forward
    and the whole second attention.

        a0 = x  + attn_0(rms(x));   h0 = rms(a0);   s = experts(h0)
        b0 = a0 + mlp_0(h0)
        a1 = b0 + attn_1(rms(b0))
        y  = a1 + mlp_1(rms(a1)) + s

    The block is GIVEN its parts: ``a0`` and ``a1`` are two dense
    ``LlamaBlock`` s of the keywords ``block`` (leaves and scopes under
    ``a0`` / ``a1`` as a Llama block has them), ``moe`` an ``ExpertShare``
    of the keywords ``experts``. Each attention keeps cache rows of its own:
    the block takes TWO layers of the pool (``cache_layers``)."""

    cache_layers = 2

    def __init__(self, experts: dict, name=None, policy=None, **block):
        super().__init__(name=name, policy=policy)
        self.experts, self.block = dict(experts), dict(block)
        self.halves = [LlamaBlock(policy=self.policy, **block)
                       for _ in range(self.cache_layers)]
        self.moe = ExpertShare(policy=self.policy, **self.experts)

    def _init(self, rng, input_shape):
        keys = jax.random.split(rng, 3)
        params = {f"a{j}": half.init(keys[j], input_shape)["params"]
                  for j, half in enumerate(self.halves)}
        params["moe"] = self.moe.init(keys[2], input_shape)["params"]
        return params, {}

    def _block(self, params, x, attention, live=None):
        """The block's equations; ``attention(j, variables, h)`` is
        sublayer ``j``'s attention of its normed input (plain, cached or
        paged: the caller's)."""
        (first, second), (p0, p1) = self.halves, (params["a0"], params["a1"])

        def attend(j, half, p, x):
            h = attention(j, {"params": p["attn"], "state": {}},
                          half._ln1(p, x))
            return half._attn_residual(p, x, h)

        with jax.named_scope("a0"):
            a0 = attend(0, first, p0, x)
            with jax.named_scope("mlp"):
                h0 = first.ln2.apply({"params": p0["ln2"], "state": {}},
                                     a0)[0]
            s, _ = self.moe.apply({"params": params["moe"], "state": {}}, h0,
                                  live=live)
            with jax.named_scope("mlp"):
                b0 = first._add(a0, first._swiglu(p0, h0, False))
        with jax.named_scope("a1"):
            y = second._mlp_residual(p1, attend(1, second, p1, b0))
            with jax.named_scope("moe_shortcut"):
                return y + s.astype(y.dtype)

    def _apply(self, params, state, x, *, train, rng):
        return self._block(
            params, x, lambda j, v, h: self.halves[j].attn.apply(
                v, h, train=train, rng=None)[0]), state

    def init_cache(self, batch: int, max_len: int, d_model: int):
        return [half.init_cache(batch, max_len, d_model)
                for half in self.halves]

    def apply_cached(self, params, x, cache, offset):
        new = list(cache)

        def attention(j, v, h):
            out, new[j] = self.halves[j].attn.apply_cached(
                v, h, cache[j], offset)
            return out

        return self._block(params, x, attention), new

    def apply_paged(self, params, x, pages_k, pages_v, block_tables, offsets,
                    layer, q_lens=None):
        """``layer``: the block's two layers of the pool, the first
        attention's and the second's."""
        pages = [pages_k, pages_v]

        def attention(j, v, h):
            out, pages[0], pages[1] = self.halves[j].attn.apply_paged(
                v, h, pages[0], pages[1], block_tables, offsets,
                layer=layer[j], q_lens=q_lens)
            return out

        y = self._block(params, x, attention,
                        _live_tokens(x, block_tables, q_lens))
        return y, pages[0], pages[1]

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        return dict(self.block, experts=dict(
            self.experts, held=list(self.experts["held"])))


_BLOCK_DEFAULTS = {"norm_eps": 1e-6, "norm_unit_offset": False,
                   "residual_f32": False, "window": None, "chunk": None,
                   "latent": None, "experts": None, "gated": None,
                   "sandwich": False, "linear": None, "scales": None}

# the kinds of layer (``layer_types``) that keep a state in the pool's state
# slots and no pages; every other kind has pages
STATE_KINDS = ("linear_attention", "mamba")


def _scale(m, key: str) -> Optional[float]:
    """One of a model's (or a block's) published multipliers (``scales``),
    None where it has none."""
    return (m.scales or {}).get(key)


def _block_options(m):
    """The block's options that differ from Llama's own, for ``_config``."""
    return {k: getattr(m, k) for k, v in _BLOCK_DEFAULTS.items()
            if getattr(m, k) != v}


@register_module("llama")
class Llama(PagedDecoder, Module):
    """Decoder-only LM: wte -> n x LlamaBlock -> RMSNorm -> head.

    No positional-embedding table — positions enter through RoPE inside
    attention, so ``max_len`` bounds only the decode cache, not a learned
    parameter.

    The family's other members are configurations of this class and its
    block, not classes of their own. EvaByte (:func:`evabyte`): RMSNorm with
    a unit offset, the residual sum in float32, an untied head of
    ``num_pred_heads`` x vocab columns of which the first vocab (the next
    token's) are served, and EVA attention (``window``, ``chunk``:
    nn/attention.py). Every member serves against the paged pool through
    ``PagedDecoder``."""

    def __init__(self, vocab_size: int = 32000, max_len: int = 2048,
                 num_layers: int = 12, d_model: int = 768, num_heads: int = 12,
                 num_kv_heads: Optional[int] = None,
                 mlp_hidden: Optional[int] = None,
                 rope_theta: Optional[float] = 10000.0, backend: str = "xla",
                 tie_embeddings: bool = True,
                 kv_cache_dtype: Optional[str] = None,
                 norm_eps: float = 1e-6, norm_unit_offset: bool = False,
                 residual_f32: bool = False, window: Optional[int] = None,
                 chunk: Optional[int] = None, num_pred_heads: int = 1,
                 latent: Optional[dict] = None,
                 experts: Optional[dict] = None,
                 gated: Optional[dict] = None, sandwich: bool = False,
                 embed_scale: bool = False, num_dense_layers: int = 0,
                 dense_hidden: Optional[int] = None, shortcut: bool = False,
                 linear: Optional[dict] = None,
                 layer_types: Optional[list] = None,
                 scales: Optional[dict] = None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        # ``layer_types``: a kind for each layer, as the model publishes
        # them, for a model WITHOUT ``gated`` keywords (a gated model's are
        # ``gated["layer_types"]``): one of ``STATE_KINDS`` keeps a state,
        # "attention" is the plain attention a block builds by default
        self.layer_types = list(layer_types) if layer_types else None
        if self.layer_types and gated:
            raise ValueError("a gated model's layer_types are gated's")
        # ``linear``: the keywords of a state mixer (``nn.attention.
        # state_mixer``), for the layers whose kind is one of
        # ``STATE_KINDS``: they keep a state in the pool's state slots
        # (``state_group``), the others pages
        self.linear = dict(linear) if linear else None
        if self.linear and not (gated or self.layer_types):
            raise ValueError("linear layers are named by layer_types")
        # ``scales``: the multipliers a model publishes beside its weights
        # (Granite's four), ONE mapping: ``embedding`` (the embedding times
        # it), ``residual`` and ``attention`` (the blocks': each sublayer's
        # output times it; the softmax's scale), ``logits`` (the head's
        # output DIVIDED by it). Absent: none of them
        self.scales = dict(scales) if scales else None
        # ``shortcut``: every block is a ``ShortcutBlock``: two attention
        # sublayers and two dense feed-forwards of ``mlp_hidden`` with the
        # model's ``experts`` across them; ``num_layers`` counts BLOCKS, the
        # pool's layers are ``cache_layers``
        self.shortcut = bool(shortcut)
        if self.shortcut and not experts:
            raise ValueError("a shortcut block is given its experts")
        self.latent = dict(latent) if latent else None
        self.experts = dict(experts, held=list(experts["held"])) \
            if experts else None
        # ``gated``: ``head_dim``, ``window``, ``rope_theta`` and
        # ``layer_types``, one of "sliding_attention" / "full_attention" a
        # layer: window layers beside global layers in ONE model
        # (nn.attention.GatedAttention; the pool then holds two groups of
        # page, ``page_groups``). ``rope_full``: the global layers are
        # rotated too; ``rotary_dim`` / ``norm_unit_offset`` pass through
        self.gated = dict(gated, layer_types=list(gated["layer_types"])) \
            if gated else None
        self.sandwich = bool(sandwich)
        # the embedding times sqrt(d_model) (muP)
        self.embed_scale = bool(embed_scale)
        # the first layers' feed-forward is dense, of ``dense_hidden``, in a
        # model whose others hold ``experts``
        self.num_dense_layers = int(num_dense_layers)
        self.dense_hidden = int(dense_hidden) if dense_hidden else None
        self.norm_eps = float(norm_eps)
        self.norm_unit_offset = bool(norm_unit_offset)
        self.residual_f32 = bool(residual_f32)
        self.window = int(window) if window else None
        self.chunk = int(chunk) if chunk else None
        self.num_pred_heads = int(num_pred_heads)
        if self.num_pred_heads > 1 and tie_embeddings:
            raise ValueError("more than one output head needs an untied head")
        self.vocab_size = int(vocab_size)
        self.max_len = int(max_len)
        self.num_layers = int(num_layers)
        self.d_model = int(d_model)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        # Llama's ~8/3 * d, rounded up to a multiple of 128 (MXU lane width)
        self.mlp_hidden = int(mlp_hidden) if mlp_hidden else (
            (8 * self.d_model // 3 + 127) // 128 * 128)
        self.rope_theta = float(rope_theta) if rope_theta else None
        self.backend = backend
        self.tie_embeddings = bool(tie_embeddings)
        self.kv_cache_dtype = kv_cache_dtype
        p = self.policy
        self.wte = Embedding(vocab_size, d_model, policy=p)
        if self.kinds and len(self.kinds) != self.num_layers:
            raise ValueError("layer_types names a kind for each of the "
                             f"{self.num_layers} layers")
        self.head_dim = int(self.gated["head_dim"]) if self.gated \
            else self.d_model // self.num_heads
        kind = ShortcutBlock if self.shortcut else LlamaBlock
        self.blocks = [kind(num_heads=num_heads,
                            num_kv_heads=self.num_kv_heads,
                            rope_theta=rope_theta, backend=backend,
                            kv_cache_dtype=kv_cache_dtype, norm_eps=norm_eps,
                            norm_unit_offset=norm_unit_offset,
                            residual_f32=residual_f32, window=window,
                            chunk=chunk, latent=latent, sandwich=sandwich,
                            policy=p, **self._layer_options(i))
                       for i in range(num_layers)]
        if self.latent:     # one cached row a token, no head axis
            from ..ops.pallas.mla_attention import row_width

            self.num_kv_heads = 1
            self.latent_row = row_width(self.latent["kv_rank"]
                                        + self.latent["rope_dim"])
        self.ln_f = RMSNorm(eps=norm_eps, unit_offset=norm_unit_offset,
                            policy=p)

    @property
    def kinds(self) -> Optional[list]:
        """The kind of each layer, where the model names them."""
        return self.gated["layer_types"] if self.gated else self.layer_types

    def _layer_options(self, i: int) -> dict:
        """What layer ``i`` is given that its neighbour may not be: its
        feed-forward (dense of ``dense_hidden`` among the first
        ``num_dense_layers``, else the model's; a shortcut block's dense
        feed-forwards are the model's and its experts go across them) and
        its kind of gated attention."""
        dense = i < self.num_dense_layers and not self.shortcut
        opts = dict(
            mlp_hidden=self.dense_hidden if dense and self.dense_hidden
            else self.mlp_hidden,
            experts=None if dense else self.experts)
        if self.scales:
            opts["scales"] = self.scales
        if self.kinds and self.kinds[i] in STATE_KINDS:
            opts["linear"] = self.linear
        elif self.gated:
            sliding = self.gated["layer_types"][i] == "sliding_attention"
            rotate = sliding or self.gated.get("rope_full")
            opts["gated"] = dict(
                head_dim=self.gated["head_dim"],
                window=self.gated["window"] if sliding else None,
                rope_theta=self.gated["rope_theta"] if rotate else None)
            for key in ("rotary_dim", "norm_unit_offset"):
                if self.gated.get(key):
                    opts["gated"][key] = self.gated[key]
        return opts

    @property
    def page_groups(self) -> Optional[dict]:
        """What the pool holds for a model of window layers beside global
        ones (``serving.kv_pool``): the window, and how many layers of each
        kind share a page of their group. None: one table for every layer."""
        if not self.gated or self.linear:
            return None
        kinds = self.gated["layer_types"]
        n_win = kinds.count("sliding_attention")
        return dict(window=int(self.gated["window"]), window_layers=n_win,
                    full_layers=len(kinds) - n_win)

    @property
    def state_group(self) -> Optional[dict]:
        """What the pool holds beside its pages for a model with state
        layers (``serving.kv_pool.StateSlots``): how many layers keep a
        state, and what the mixer, whichever it is, says a row's is in one
        of them: the conv positions (``conv_rows``) and the recurrent state
        (``rec_shape``). None: pages only."""
        if not self.linear:
            return None
        mix = next(b.attn for b in self.blocks if b.linear)
        return dict(layers=sum(bool(b.linear) for b in self.blocks),
                    conv=mix.conv_rows, rec=mix.rec_shape)

    def _paged_layers(self, pages_k, block_tables):
        """A packed step table of two page groups, by layer: ``[a segment a
        global layer | a segment a window layer | base]`` (``kv_pool``: Two
        page groups); every layer's pages lie in the pool's ONE layer."""
        if self.linear:
            # the packed table's LAST entry is the row's state slot; the
            # layers with pages share the entries before it, each its own
            # layer of the pool
            slots, tables = block_tables[:, -1], block_tables[:, :-1]
            out, at_state, at_pages = [], 0, 0
            for block in self.blocks:
                if block.linear:
                    out.append(dict(slots=slots, state_layer=at_state))
                    at_state += 1
                else:
                    out.append(dict(block_tables=tables, layer=at_pages))
                    at_pages += 1
            return out
        if not self.gated:
            return super()._paged_layers(pages_k, block_tables)
        from ..ops.pallas.paged_attention import (group_segments,
                                                  window_table_pages)

        groups = self.page_groups
        ww = window_table_pages(groups["window"], pages_k.shape[-2])
        wg, at_full, at_win = group_segments(
            block_tables.shape[1], groups["full_layers"],
            groups["window_layers"], ww)
        at_full, at_win = iter(at_full), iter(at_win)
        base = block_tables[:, -1]
        out = []
        for kind in self.gated["layer_types"]:
            if kind == "sliding_attention":
                at = next(at_win)
                out.append(dict(block_tables=block_tables[:, at:at + ww],
                                layer=0, table_base=base))
            else:
                at = next(at_full)
                out.append(dict(block_tables=block_tables[:, at:at + wg],
                                layer=0))
        return out

    def _init(self, rng, input_shape):
        n, s = input_shape[:2]
        keys = jax.random.split(rng, self.num_layers + 3)
        emb_shape = (n, s, self.d_model)
        params = {
            "wte": self.wte.init(keys[0], input_shape)["params"],
            "ln_f": self.ln_f.init(keys[1], emb_shape)["params"],
        }
        for i, block in enumerate(self.blocks):
            params[f"h{i}"] = block.init(keys[3 + i], emb_shape)["params"]
        if not self.tie_embeddings:
            head = Dense(self.vocab_size * self.num_pred_heads,
                         use_bias=False, policy=self.policy)
            params["head"] = head.init(keys[2], emb_shape)["params"]
        return params, {}

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        logits = self._head_product(params, x)
        if _scale(self, "logits"):
            logits = logits / jnp.asarray(_scale(self, "logits"),
                                          logits.dtype)
        return logits

    def _head_product(self, params, x):
        if self.tie_embeddings:
            return self.wte.attend(params["wte"], x)
        from ..ops.pallas.quant_matmul import qmatmul

        # of several output heads the first (the next token's) is served
        w = self.policy.cast_param(
            params["head"]["kernel"])[:, :self.vocab_size]
        return qmatmul(self.policy.cast_in(x), w, out_dtype=jnp.float32)

    @jax.named_scope("embed")
    def _embed(self, params, ids, offsets=None):
        """Token embeddings (positions enter through RoPE, not here); the
        residual stream starts in float32 where the model keeps it so."""
        x, _ = self.wte.apply({"params": params["wte"], "state": {}}, ids)
        x = x.astype(jnp.float32) if self.residual_f32 else x
        if self.embed_scale:
            x = x * jnp.asarray(self.d_model ** 0.5, x.dtype)
        if _scale(self, "embedding"):
            x = x * jnp.asarray(_scale(self, "embedding"), x.dtype)
        return x

    @jax.named_scope("ln_f")
    def _ln_f(self, params, x):
        return self.ln_f.apply({"params": params["ln_f"], "state": {}}, x)[0]

    def _hidden(self, params, ids, train, rng):
        keys = rnglib.split_for(rng, self.num_layers)
        x = self._embed(params, ids)
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, _ = block.apply(
                    {"params": params[f"h{i}"], "state": {}}, x,
                    train=train, rng=keys[i])
        return self._ln_f(params, x)

    def _apply(self, params, state, ids, *, train, rng):
        return self._head(params, self._hidden(params, ids, train, rng)), state

    def apply_hidden(self, variables, ids, *, train=False, rng=None):
        """Post-ln_f hidden without the head matmul (chunked LM-head loss)."""
        return self._hidden(variables["params"], ids, train, rng), {}

    def head_table(self, params):
        if self.tie_embeddings:
            return self.policy.cast_param(params["wte"]["table"])
        return self.policy.cast_param(params["head"]["kernel"]).T

    def output_shape(self, input_shape):
        return tuple(input_shape[:2]) + (self.vocab_size,)

    # -- KV-cache decode (generate() drives these, duck-typed) ---------------

    def init_cache(self, batch: int, max_len: Optional[int] = None):
        max_len = max_len or self.max_len
        return [b.init_cache(batch, max_len, self.d_model) for b in self.blocks]

    def apply_cached(self, params, ids, caches, offset):
        x = self._embed(params, ids)
        new_caches = []
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, c = block.apply_cached(params[f"h{i}"], x, caches[i],
                                          offset)
            new_caches.append(c)
        return self._head(params, self._ln_f(params, x)), new_caches

    def _config(self):
        cfg = {"vocab_size": self.vocab_size, "max_len": self.max_len,
               "num_layers": self.num_layers, "d_model": self.d_model,
               "num_heads": self.num_heads, "mlp_hidden": self.mlp_hidden,
               "rope_theta": self.rope_theta, "backend": self.backend,
               "tie_embeddings": self.tie_embeddings}
        if self.num_kv_heads != self.num_heads:
            cfg["num_kv_heads"] = self.num_kv_heads
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        cfg.update(_block_options(self))
        if self.num_pred_heads != 1:
            cfg["num_pred_heads"] = self.num_pred_heads
        for key in ("embed_scale", "num_dense_layers", "dense_hidden",
                    "shortcut", "layer_types"):
            if getattr(self, key):
                cfg[key] = getattr(self, key)
        return cfg


def evabyte(num_layers: int = 16, **kw):
    """EvaByte (https://huggingface.co/EvaByte/EvaByte, config.json) as one
    chip of a two-stage pipeline serves it: 16 of the 32 layers, every width
    as published (4,096 wide, 32 heads of 128, feed-forward 11,008, the 320
    bytes, 32,768 positions, window 2,048, chunk 16, 8 output heads), bf16
    weights. ``num_layers=32`` is the whole model (13 GB: no room for a
    batch on a 16 GB chip)."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    cfg = dict(vocab_size=320, max_len=32768, d_model=4096, num_heads=32,
               mlp_hidden=11008, window=2048, chunk=16, num_pred_heads=8)
    cfg.update(kw)
    return Llama(num_layers=num_layers, rope_theta=100000.0,
                 tie_embeddings=False, norm_eps=1e-5, norm_unit_offset=True,
                 residual_f32=True, **cfg)


def evabyte_tiny(**kw):
    """EvaByte's block at test sizes: 2 layers, 64 wide, 4 heads of 16,
    window 32, chunk 4, 2 output heads, 256 positions."""
    cfg = dict(num_layers=2, d_model=64, num_heads=4, mlp_hidden=128,
               window=32, chunk=4, num_pred_heads=2, max_len=256)
    cfg.update(kw)
    return evabyte(**cfg)


def mistral_small4(num_layers: int = 6, held_experts: int = 32,
                   vocab: int = 32768, **kw):
    """Mistral-Small-4-119B-2603 (https://huggingface.co/mistralai/
    Mistral-Small-4-119B-2603, config.json) as ONE chip of four that share
    each layer serves it: 6 of the 36 layers, every width as published
    (4,096 wide, 32 heads, latent attention with ``q_lora_rank`` 1,024,
    ``kv_lora_rank`` 256, head dims 64 + 64 and 128, YaRN rotary), the
    router over all 128 experts and 4 a token, experts 0 .. ``held_experts``
    - 1 of width 2,048 held here beside the shared one, rows 0 .. ``vocab``
    - 1 of the 131,072-token vocabulary, bf16 weights. The vision tower is
    not served."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    cfg = dict(
        vocab_size=vocab, max_len=1048576, d_model=4096, num_heads=32,
        mlp_hidden=2048,
        latent=dict(q_rank=1024, kv_rank=256, nope_dim=64, rope_dim=64,
                    v_dim=128, rope=dict(
                        rope_theta=10000.0, factor=128.0,
                        original_max_position_embeddings=8192, beta_fast=32,
                        beta_slow=1, mscale=1, mscale_all_dim=1,
                        llama_4_scaling_beta=0.1)),
        experts=dict(num_experts=128, held=range(held_experts), top_k=4,
                     hidden=2048, shared=1))
    cfg.update(kw)
    return Llama(num_layers=num_layers, tie_embeddings=False, norm_eps=1e-6,
                 residual_f32=True, **cfg)


def mistral_small4_tiny(**kw):
    """Mistral Small 4's block at test sizes: 2 layers, 64 wide, 4 heads,
    latent 32 + 16 (query rank 48), 8 of 16 experts of width 32 held, 4 a
    token, one shared expert; YaRN over 32 original positions. Float32
    unless told otherwise: at 64 wide and 16 experts a bf16 hidden state
    breaks a near-tie of the router the other way every few dozen tokens,
    and one flipped expert of four is a quarter of a layer's routed output,
    so a CPU rehearsal's comparison would hang on the seed."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="float32", param="float32",
                                        compute="float32"))
    cfg = dict(
        num_layers=2, vocab=256, max_len=256, d_model=64, num_heads=4,
        mlp_hidden=32,
        latent=dict(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=16, v_dim=16,
                    rope=dict(rope_theta=10000.0, factor=4.0,
                              original_max_position_embeddings=32,
                              beta_fast=32, beta_slow=1, mscale=1,
                              mscale_all_dim=1, llama_4_scaling_beta=0.1)),
        experts=dict(num_experts=16, held=range(8), top_k=4, hidden=32,
                     shared=1))
    cfg.update(kw)
    return mistral_small4(**cfg)


def trinity_large_ep8(num_layers: int = 5, num_dense_layers: int = 1,
                      held_experts: int = 32, vocab: int = 25024, **kw):
    """Trinity-Large-Preview (https://huggingface.co/arcee-ai/
    Trinity-Large-Preview, config.json, ``model_type: afmoe``) as ONE chip of
    eight that share each layer serves it: the first 5 of the 60 layers (one
    of the 6 leading dense layers, then a whole period of the pattern, three
    sliding-window layers and a global one), every width as published (3,072
    wide, 48 query heads over 8 KV heads of 128, window 4,096, dense
    feed-forward 12,288), the router over all 256 experts and 4 a token with
    sigmoid scores and a selection bias, experts 0 .. ``held_experts`` - 1
    of width 3,072 held here beside the shared one, rows 0 .. ``vocab`` - 1
    of the 200,192-token vocabulary, bf16 weights."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    period = ["sliding_attention"] * 3 + ["full_attention"]
    cfg = dict(
        vocab_size=vocab, max_len=262144, d_model=3072, num_heads=48,
        num_kv_heads=8, mlp_hidden=3072, dense_hidden=12288,
        gated=dict(head_dim=128, window=4096, rope_theta=10000.0,
                   layer_types=(period * -(-num_layers // 4))[:num_layers]),
        experts=dict(num_experts=256, held=range(held_experts), top_k=4,
                     hidden=3072, shared=1, score="sigmoid",
                     route_scale=2.448))
    cfg.update(kw)
    return Llama(num_layers=num_layers, num_dense_layers=num_dense_layers,
                 tie_embeddings=False, norm_eps=1e-5, residual_f32=True,
                 sandwich=True, embed_scale=True, **cfg)


def trinity_large_tiny(**kw):
    """Trinity Large's block at test sizes: 5 layers in the published order
    (a dense sliding layer, then sliding, sliding, full, sliding), 64 wide,
    4 query heads over 2 KV heads of 32, window 16, dense feed-forward 128,
    8 of 16 experts of width 32 held, 4 a token, one shared expert.
    Float32 unless told otherwise (``mistral_small4_tiny`` says why)."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="float32", param="float32",
                                        compute="float32"))
    cfg = dict(
        num_layers=5, vocab=256, max_len=256, d_model=64, num_heads=4,
        num_kv_heads=2, mlp_hidden=32, dense_hidden=128,
        gated=dict(head_dim=32, window=16, rope_theta=10000.0,
                   layer_types=["sliding_attention"] * 3
                   + ["full_attention", "sliding_attention"]),
        experts=dict(num_experts=16, held=range(8), top_k=4, hidden=32,
                     shared=1, score="sigmoid", route_scale=2.448))
    cfg.update(kw)
    return trinity_large_ep8(**cfg)


def longcat_flash_ep32(num_layers: int = 4, held_experts: int = 16,
                       vocab: int = 16384, **kw):
    """LongCat-Flash-Omni's language model (https://huggingface.co/
    meituan-longcat/LongCat-Flash-Omni, config.json) as ONE chip of 32 that
    share each layer serves it: 4 of the 28 blocks, every width as published
    (6,144 wide, 64 heads; a block of two latent attentions, ``q_lora_rank``
    1,536, ``kv_lora_rank`` 512, head dims 128 + 64 and 128, both rank
    scales, plain rotary at theta 1e7, and two dense feed-forwards of
    12,288 with one shortcut expert layer across them), the softmax router
    over all 768 ids (512 experts + 256 zero-compute identity experts) and
    12 a token, selected with the bias, weighted without it, not
    renormalised, times 6; experts 0 .. ``held_experts`` - 1 of width 2,048
    held here, rows 0 .. ``vocab`` - 1 of the 131,072-token vocabulary, bf16
    weights. The audio and vision towers and the codec decoder are not
    served."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    cfg = dict(
        vocab_size=vocab, max_len=131072, d_model=6144, num_heads=64,
        mlp_hidden=12288,
        latent=dict(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                    v_dim=128, rope=dict(rope_theta=10000000.0)),
        experts=dict(num_experts=512, zero_experts=256,
                     held=range(held_experts), top_k=12, hidden=2048,
                     score="softmax_raw", route_scale=6.0))
    cfg.update(kw)
    d, lat = cfg["d_model"], cfg["latent"]
    # mla_scale_q_lora / mla_scale_kv_lora
    cfg["latent"] = dict(lat, q_scale=(d / lat["q_rank"]) ** 0.5,
                         kv_scale=(d / lat["kv_rank"]) ** 0.5)
    return Llama(num_layers=num_layers, tie_embeddings=False, norm_eps=1e-5,
                 residual_f32=True, shortcut=True, **cfg)


def longcat_flash_tiny(**kw):
    """LongCat-Flash's block at test sizes: 2 blocks (4 cache layers), 64
    wide, 4 heads, latent 32 + 16 (query rank 48: rank scales 1.155 and
    1.414), dense feed-forwards of 128, a router of 24 (16 experts + 8
    zero-compute) and 6 a token, 8 experts of width 32 held. Float32 unless
    told otherwise (``mistral_small4_tiny`` says why)."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="float32", param="float32",
                                        compute="float32"))
    cfg = dict(
        num_layers=2, vocab=256, max_len=256, d_model=64, num_heads=4,
        mlp_hidden=128,
        latent=dict(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=16, v_dim=16,
                    rope=dict(rope_theta=10000.0)),
        experts=dict(num_experts=16, zero_experts=8, held=range(8), top_k=6,
                     hidden=32, score="softmax_raw", route_scale=6.0))
    cfg.update(kw)
    return longcat_flash_ep32(**cfg)


def qwen3_next_ep4(num_layers: int = 8, held_experts: int = 128,
                   vocab: int = 37984, **kw):
    """Qwen3-Next-80B-A3B-Instruct (https://huggingface.co/Qwen/
    Qwen3-Next-80B-A3B-Instruct, config.json, ``model_type: qwen3_next``) as
    ONE chip of four that share each layer serves it: the first 8 of the 48
    layers (two whole periods of three Gated DeltaNet layers and a gated
    full-attention layer, ``full_attention_interval`` 4), every width as
    published (2,048 wide; the linear layers 16 key heads and 32 value heads
    of 128 behind a convolution of 4; the full layers 16 query heads over 2
    KV heads of 256, unit-offset head norms, rotary over the first 64 lanes
    at theta 1e7, a sigmoid gate on the output), the softmax router over all
    512 experts and 10 a token, renormalised, experts 0 .. ``held_experts``
    - 1 of width 512 held here beside the shared one and its sigmoid gate,
    rows 0 .. ``vocab`` - 1 of the 151,936-token vocabulary, bf16 weights,
    RMSNorm with a unit offset. The multi-token-prediction head is not
    served."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    cfg = dict(
        vocab_size=vocab, max_len=262144, d_model=2048, num_heads=16,
        num_kv_heads=2, mlp_hidden=512, full_attention_interval=4,
        gated=dict(head_dim=256, window=None, rope_theta=10000000.0,
                   rope_full=True, rotary_dim=64, norm_unit_offset=True),
        linear=dict(key_heads=16, value_heads=32, key_dim=128, value_dim=128,
                    conv=4),
        experts=dict(num_experts=512, held=range(held_experts), top_k=10,
                     hidden=512, shared=1, shared_gated=True))
    cfg.update(kw)
    every = cfg.pop("full_attention_interval")
    cfg["gated"] = dict(cfg["gated"], layer_types=[
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(num_layers)])
    return Llama(num_layers=num_layers, tie_embeddings=False, norm_eps=1e-6,
                 norm_unit_offset=True, residual_f32=True, **cfg)


def qwen3_next_tiny(**kw):
    """Qwen3-Next's block at test sizes: 4 layers (linear, linear, linear,
    full), 64 wide; the linear layers 2 key heads and 4 value heads of 16;
    the full layer 4 query heads over 2 KV heads of 32, rotary over 8 lanes;
    8 of 16 experts of width 32 held, 4 a token, a gated shared expert.
    Float32 unless told otherwise (``mistral_small4_tiny`` says why)."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="float32", param="float32",
                                        compute="float32"))
    cfg = dict(
        num_layers=4, vocab=256, max_len=512, d_model=64, num_heads=4,
        num_kv_heads=2, mlp_hidden=32,
        gated=dict(head_dim=32, window=None, rope_theta=10000.0,
                   rope_full=True, rotary_dim=8, norm_unit_offset=True),
        linear=dict(key_heads=2, value_heads=4, key_dim=16, value_dim=16,
                    conv=4),
        experts=dict(num_experts=16, held=range(8), top_k=4, hidden=32,
                     shared=1, shared_gated=True))
    cfg.update(kw)
    return qwen3_next_ep4(**cfg)


def granite4_h_micro(**kw):
    """granite-4.0-h-micro (https://huggingface.co/ibm-granite/
    granite-4.0-h-micro, config.json, ``model_type: granitemoehybrid``),
    WHOLE: 40 layers in the published order, 36 Mamba-2 mixers (64 heads of
    64 with a state of 128 behind a convolution of 4 with a bias, one group)
    and, at layers 5, 15, 25 and 35, plain grouped-query attention (32 query
    heads over 8 KV heads of 64) WITHOUT positions
    (``position_embedding_type: "nope"``) at the published softmax scale
    1/64; a gated SiLU feed-forward of 8,192 in every layer (no experts);
    the embedding times 12, each sublayer's output times 0.22, the tied
    head's logits over all 100,352 rows divided by 8; plain RMSNorm at 1e-5;
    bf16 weights: 3.19 G parameters, 6.4 GB."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="bfloat16", param="bfloat16",
                                        compute="bfloat16"))
    cfg = dict(
        vocab_size=100352, max_len=131072, num_layers=40, d_model=2048,
        num_heads=32, num_kv_heads=8, mlp_hidden=8192,
        layer_types=["attention" if i % 10 == 5 else "mamba"
                     for i in range(40)],
        linear=dict(mixer="mamba2", heads=64, head_dim=64, state=128, conv=4),
        scales=dict(embedding=12.0, residual=0.22, attention=0.015625,
                    logits=8.0))
    cfg.update(kw)
    return Llama(rope_theta=None, tie_embeddings=True, norm_eps=1e-5,
                 residual_f32=True, **cfg)


def granite4_h_micro_cpu(**kw):
    """granite-4.0-h-micro's layers at test sizes (NOT Granite-4.0-H-Tiny,
    which is another published model): 5 layers (mamba, mamba, attention,
    mamba, mamba), 64 wide; the mixers 8 heads of 16 with a state of 16; the
    attention 4 query heads over 2 KV heads of 16 at softmax scale 1/8
    (twice ``16^-1/2``, so a dropped scale shows); feed-forward 128; the
    four multipliers as published. Float32 unless told otherwise."""
    from ..core.dtypes import DTypePolicy

    kw.setdefault("policy", DTypePolicy(io="float32", param="float32",
                                        compute="float32"))
    cfg = dict(
        vocab_size=256, max_len=512, num_layers=5, d_model=64, num_heads=4,
        num_kv_heads=2, mlp_hidden=128,
        layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
        linear=dict(mixer="mamba2", heads=8, head_dim=16, state=16, conv=4),
        scales=dict(embedding=12.0, residual=0.22, attention=0.125,
                    logits=8.0))
    cfg.update(kw)
    return granite4_h_micro(**cfg)


def llama_small(**kw):
    """12L/768d, 12 q heads / 4 kv heads, SwiGLU 2048 — GPT-2-small-scale
    Llama geometry for from-scratch training."""
    return Llama(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 **kw)


def llama_1b(**kw):
    """16L/2048d, 32 q heads (D=64) / 8 kv heads — a ~1B geometry."""
    return Llama(num_layers=16, d_model=2048, num_heads=32, num_kv_heads=8,
                 **kw)
