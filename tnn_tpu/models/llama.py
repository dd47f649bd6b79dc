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
from ..nn.attention import MultiHeadAttention
from ..nn.embedding import Embedding
from ..nn.layers import Dense
from ..nn.norms import RMSNorm


@register_module("llama_block")
class LlamaBlock(Module):
    """Pre-RMSNorm decoder block: x + attn(rms(x)); x + swiglu(rms(x)).

    SwiGLU MLP: down( silu(gate(h)) * up(h) ) — three bias-free projections
    with an explicit ``mlp_hidden`` width (Llama uses ~8/3 * d rounded, not
    the GPT 4x)."""

    def __init__(self, num_heads: int, mlp_hidden: int,
                 num_kv_heads: Optional[int] = None,
                 rope_theta: float = 10000.0, backend: str = "xla",
                 kv_cache_dtype: Optional[str] = None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.num_heads = int(num_heads)
        self.mlp_hidden = int(mlp_hidden)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        self.rope_theta = float(rope_theta)
        self.backend = backend
        self.kv_cache_dtype = kv_cache_dtype
        p = self.policy
        self.ln1 = RMSNorm(policy=p)
        self.attn = MultiHeadAttention(
            num_heads, causal=True, backend=backend,
            num_kv_heads=self.num_kv_heads, rope_theta=self.rope_theta,
            use_bias=False, kv_cache_dtype=kv_cache_dtype, policy=p)
        self.ln2 = RMSNorm(policy=p)
        self.gate = Dense(self.mlp_hidden, use_bias=False, policy=p)
        self.up = Dense(self.mlp_hidden, use_bias=False, policy=p)
        # the down projection needs the model dim, known only at init —
        # constructed per use like GPTBlock._mlp_layers

    def _init(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2, k3, k4, k5, k6 = jax.random.split(rng, 6)
        down = Dense(d, use_bias=False, policy=self.policy)
        hidden_shape = tuple(input_shape[:-1]) + (self.mlp_hidden,)
        return {
            "ln1": self.ln1.init(k1, input_shape)["params"],
            "attn": self.attn.init(k2, input_shape)["params"],
            "ln2": self.ln2.init(k3, input_shape)["params"],
            "gate": self.gate.init(k4, input_shape)["params"],
            "up": self.up.init(k5, input_shape)["params"],
            "down": down.init(k6, hidden_shape)["params"],
        }, {}

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

    def _apply(self, params, state, x, *, train, rng):
        k1 = rnglib.split_for(rng, 1)[0]
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, _ = self.attn.apply({"params": params["attn"], "state": {}}, h,
                               train=train, rng=k1)
        x = x + h
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        return x + self._swiglu(params, h, train), state

    # -- cached decode --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, d_model: int):
        return self.attn.init_cache(batch, max_len, d_model)

    def apply_cached(self, params, x, cache, offset):
        h, _ = self.ln1.apply({"params": params["ln1"], "state": {}}, x)
        h, new_cache = self.attn.apply_cached({"params": params["attn"]}, h,
                                              cache, offset)
        x = x + h
        h, _ = self.ln2.apply({"params": params["ln2"], "state": {}}, x)
        return x + self._swiglu(params, h, False), new_cache

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def _config(self):
        cfg = {"num_heads": self.num_heads, "mlp_hidden": self.mlp_hidden,
               "rope_theta": self.rope_theta, "backend": self.backend}
        if self.num_kv_heads != self.num_heads:
            cfg["num_kv_heads"] = self.num_kv_heads
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        return cfg


@register_module("llama")
class Llama(Module):
    """Decoder-only LM: wte -> n x LlamaBlock -> RMSNorm -> head.

    No positional-embedding table — positions enter through RoPE inside
    attention, so ``max_len`` bounds only the decode cache, not a learned
    parameter."""

    def __init__(self, vocab_size: int = 32000, max_len: int = 2048,
                 num_layers: int = 12, d_model: int = 768, num_heads: int = 12,
                 num_kv_heads: Optional[int] = None,
                 mlp_hidden: Optional[int] = None,
                 rope_theta: float = 10000.0, backend: str = "xla",
                 tie_embeddings: bool = True,
                 kv_cache_dtype: Optional[str] = None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.vocab_size = int(vocab_size)
        self.max_len = int(max_len)
        self.num_layers = int(num_layers)
        self.d_model = int(d_model)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        # Llama's ~8/3 * d, rounded up to a multiple of 128 (MXU lane width)
        self.mlp_hidden = int(mlp_hidden) if mlp_hidden else (
            (8 * self.d_model // 3 + 127) // 128 * 128)
        self.rope_theta = float(rope_theta)
        self.backend = backend
        self.tie_embeddings = bool(tie_embeddings)
        self.kv_cache_dtype = kv_cache_dtype
        p = self.policy
        self.wte = Embedding(vocab_size, d_model, policy=p)
        self.blocks = [LlamaBlock(num_heads, self.mlp_hidden,
                                  num_kv_heads=self.num_kv_heads,
                                  rope_theta=rope_theta, backend=backend,
                                  kv_cache_dtype=kv_cache_dtype, policy=p)
                       for _ in range(num_layers)]
        self.ln_f = RMSNorm(policy=p)

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
            head = Dense(self.vocab_size, use_bias=False, policy=self.policy)
            params["head"] = head.init(keys[2], emb_shape)["params"]
        return params, {}

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        if self.tie_embeddings:
            return self.wte.attend(params["wte"], x)
        from ..ops.pallas.quant_matmul import qmatmul

        w = self.policy.cast_param(params["head"]["kernel"])
        return qmatmul(x, w, out_dtype=jnp.float32)

    def _hidden(self, params, ids, train, rng):
        keys = rnglib.split_for(rng, self.num_layers)
        with jax.named_scope("embed"):
            x, _ = self.wte.apply({"params": params["wte"], "state": {}}, ids)
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, _ = block.apply(
                    {"params": params[f"h{i}"], "state": {}}, x,
                    train=train, rng=keys[i])
        with jax.named_scope("ln_f"):
            x, _ = self.ln_f.apply({"params": params["ln_f"], "state": {}}, x)
        return x

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
        with jax.named_scope("embed"):
            x, _ = self.wte.apply({"params": params["wte"], "state": {}}, ids)
        new_caches = []
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, c = block.apply_cached(params[f"h{i}"], x, caches[i],
                                          offset)
            new_caches.append(c)
        with jax.named_scope("ln_f"):
            x, _ = self.ln_f.apply({"params": params["ln_f"], "state": {}}, x)
        return self._head(params, x), new_caches

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
        return cfg


def llama_small(**kw):
    """12L/768d, 12 q heads / 4 kv heads, SwiGLU 2048 — GPT-2-small-scale
    Llama geometry for from-scratch training."""
    return Llama(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 **kw)


def llama_1b(**kw):
    """16L/2048d, 32 q heads (D=64) / 8 kv heads — a ~1B geometry."""
    return Llama(num_layers=16, d_model=2048, num_heads=32, num_kv_heads=8,
                 **kw)
