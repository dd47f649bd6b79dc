"""GPT-2 model family.

Parity: reference gpt2_{small,medium,large} builders (src/nn/example_models.cpp:384-504;
small = 12L/768d/12h/1024ctx/50257vocab at :385-391) and the gpt_block DSL entry
(include/nn/layer_builder.hpp:531-570). "flash" variants map to backend="pallas".

Exceeds the reference: KV-cache greedy/sampled generation (the reference recomputes the
full 1024-token sequence per generated token, examples/gpt2_inference.cpp:71-91) and
weight tying between token embedding and output head.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import rng as rnglib
from ..core.module import Module, register_module
from ..nn.embedding import Embedding, PositionalEmbedding
from ..nn.layers import Dense, Dropout
from ..nn.norms import LayerNorm
from ..nn.transformer import GPTBlock, PagedDecoder


@register_module("gpt2")
class GPT2(PagedDecoder, Module):
    """Decoder-only LM: wte + wpe -> n_layer x GPTBlock -> ln_f -> logits (tied head).
    Serves against the paged pool through ``PagedDecoder``."""

    def __init__(self, vocab_size: int = 50257, max_len: int = 1024, num_layers: int = 12,
                 d_model: int = 768, num_heads: int = 12, dropout: float = 0.0,
                 backend: str = "xla", tie_embeddings: bool = True,
                 moe_experts: int = 0, num_kv_heads=None,
                 kv_cache_dtype=None, name=None, policy=None):
        super().__init__(name=name, policy=policy)
        self.vocab_size = int(vocab_size)
        self.max_len = int(max_len)
        self.num_layers = int(num_layers)
        self.d_model = int(d_model)
        self.num_heads = int(num_heads)
        self.dropout = float(dropout)
        self.backend = backend
        self.tie_embeddings = bool(tie_embeddings)
        self.moe_experts = int(moe_experts)  # >0: MoE FFN in every block
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads else self.num_heads
        self.kv_cache_dtype = kv_cache_dtype
        p = self.policy
        self.wte = Embedding(vocab_size, d_model, policy=p)
        self.wpe = PositionalEmbedding(max_len, policy=p)
        self.drop = Dropout(dropout, policy=p)
        self.blocks = [GPTBlock(num_heads, dropout=dropout, backend=backend,
                                moe_experts=moe_experts,
                                num_kv_heads=self.num_kv_heads,
                                kv_cache_dtype=kv_cache_dtype, policy=p)
                       for _ in range(num_layers)]
        self.ln_f = LayerNorm(policy=p)

    def _init(self, rng, input_shape):
        n, s = input_shape[:2]
        keys = jax.random.split(rng, self.num_layers + 3)
        emb_shape = (n, s, self.d_model)
        params = {
            "wte": self.wte.init(keys[0], input_shape)["params"],
            "wpe": self.wpe.init(keys[1], emb_shape)["params"],
            "ln_f": self.ln_f.init(keys[2], emb_shape)["params"],
        }
        state = {}
        for i, block in enumerate(self.blocks):
            bv = block.init(keys[3 + i], emb_shape)
            params[f"h{i}"] = bv["params"]
            if bv["state"]:  # MoE blocks carry aux-loss state
                state[f"h{i}"] = bv["state"]
        if not self.tie_embeddings:
            head = Dense(self.vocab_size, use_bias=False, policy=self.policy)
            params["head"] = head.init(keys[2], emb_shape)["params"]
        return params, state

    @jax.named_scope("embed")
    def _trunk(self, params, ids, train, rng, offset=0):
        keys = rnglib.split_for(rng, self.num_layers + 1)
        x, _ = self.wte.apply({"params": params["wte"], "state": {}}, ids)
        x, _ = self.wpe.apply({"params": params["wpe"], "state": {}}, x, offset=offset)
        x, _ = self.drop.apply({}, x, train=train, rng=keys[-1])
        return x, keys

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        if self.tie_embeddings:
            logits = self.wte.attend(params["wte"], x)
        else:
            from ..ops.pallas.quant_matmul import qmatmul

            w = self.policy.cast_param(params["head"]["kernel"])
            logits = qmatmul(x, w, out_dtype=jnp.float32)
        return logits  # f32 logits for a stable softmax/loss

    def _apply(self, params, state, ids, *, train, rng):
        x, new_state = self._hidden(params, state, ids, train, rng)
        return self._head(params, x), new_state

    def _hidden(self, params, state, ids, train, rng):
        x, keys = self._trunk(params, ids, train, rng)
        new_state = {}
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, st = block.apply(
                    {"params": params[f"h{i}"],
                     "state": state.get(f"h{i}", {})},
                    x, train=train, rng=keys[i])
            if st:
                new_state[f"h{i}"] = st
        return self._ln_f(params, x), new_state

    @jax.named_scope("ln_f")
    def _ln_f(self, params, x):
        return self.ln_f.apply({"params": params["ln_f"], "state": {}}, x)[0]

    def apply_hidden(self, variables, ids, *, train=False, rng=None):
        """(N, S) ids -> post-ln_f hidden (N, S, D), WITHOUT the head matmul.

        The entry point for fused LM-head losses (nn.lm_loss.lm_head_loss):
        the loss contracts hidden against the head table in vocab chunks
        instead of materializing (N*S, vocab) f32 logits."""
        x, new_state = self._hidden(variables["params"],
                                    variables.get("state", {}) or {},
                                    ids, train, rng)
        return x, new_state

    def head_table(self, params):
        """The (V, D) matrix the head contracts against (tied or untied) —
        what lm_head_loss needs alongside apply_hidden's output."""
        if self.tie_embeddings:
            return self.policy.cast_param(params["wte"]["table"])
        return self.policy.cast_param(params["head"]["kernel"]).T

    def output_shape(self, input_shape):
        return tuple(input_shape[:2]) + (self.vocab_size,)

    # -- KV-cache decode ------------------------------------------------------

    def init_cache(self, batch: int, max_len: Optional[int] = None):
        max_len = max_len or self.max_len
        return [b.init_cache(batch, max_len, self.d_model) for b in self.blocks]

    def apply_cached(self, params, ids, caches, offset):
        """Forward ids (N, S_new) given caches covering [0, offset).

        Returns (logits for the new positions, new caches).
        """
        x, _ = self._trunk(params, ids, False, None, offset=offset)
        new_caches = []
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                x, c = block.apply_cached(params[f"h{i}"], x, caches[i],
                                          offset)
            new_caches.append(c)
        return self._head(params, self._ln_f(params, x)), new_caches

    def _embed(self, params, toks, offsets):
        """PagedDecoder's hook: token + learned position embeddings."""
        return self._trunk(params, toks, False, None, offset=offsets)[0]

    def _config(self):
        cfg = {"vocab_size": self.vocab_size, "max_len": self.max_len,
               "num_layers": self.num_layers, "d_model": self.d_model,
               "num_heads": self.num_heads, "dropout": self.dropout,
               "backend": self.backend, "tie_embeddings": self.tie_embeddings}
        if self.moe_experts:
            cfg["moe_experts"] = self.moe_experts
        if self.num_kv_heads != self.num_heads:
            cfg["num_kv_heads"] = self.num_kv_heads
        if self.kv_cache_dtype:
            cfg["kv_cache_dtype"] = self.kv_cache_dtype
        return cfg


def generate(model: GPT2, params, prompt_ids, max_new_tokens: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             max_len: Optional[int] = None, top_k: int = 0,
             top_p: float = 0.0):
    """Autoregressive generation with a KV cache, fully jit-compiled.

    Prefill processes the whole prompt in one pass; decode generates one token per step
    with lax.scan (static shapes — no per-token recompilation). temperature<=0 = greedy.
    Exceeds the reference inference loop (full recompute per token,
    examples/gpt2_inference.cpp:71-122).
    """
    prompt_ids = jnp.asarray(prompt_ids)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None]
    batch, prompt_len = prompt_ids.shape
    # Default the KV cache to the REQUEST length, not the model's max_len:
    # decode is HBM-bound and attention reads the whole padded cache every
    # step, so a 1024-wide cache on a 192-token request cost 3.9x at bs=8.
    # Pass max_len explicitly to share one compiled program across request
    # sizes (the jit cache is keyed on it).
    max_len = max_len or min(model.max_len, prompt_len + max_new_tokens)
    if prompt_len + max_new_tokens > max_len:
        raise ValueError(f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
                         f"exceeds max_len {max_len}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    # jit cache lives on the model instance — repeat calls with the same geometry reuse
    # the compiled prefill+scan program instead of retracing.
    cache_key = (batch, prompt_len, max_new_tokens, float(temperature),
                 max_len, int(top_k), float(top_p))
    jit_cache = getattr(model, "_generate_jit_cache", None)
    if jit_cache is None:
        jit_cache = model._generate_jit_cache = {}
    run = jit_cache.get(cache_key)
    if run is None:

        @jax.jit
        def run(params, prompt_ids, rng):
            caches = model.init_cache(batch, max_len)
            logits, caches = model.apply_cached(params, prompt_ids, caches, 0)
            last_logits = logits[:, -1]

            from .sampling import make_sampler

            sample = make_sampler(temperature, top_k, top_p)

            def step(carry, key):
                caches, last_logits, offset = carry
                tok = sample(last_logits, key)
                logits, caches = model.apply_cached(params, tok[:, None], caches, offset)
                return (caches, logits[:, -1], offset + 1), tok

            keys = jax.random.split(rng, max_new_tokens)
            (_, _, _), toks = jax.lax.scan(
                step, (caches, last_logits, jnp.asarray(prompt_len, jnp.int32)), keys)
            return toks.T  # (batch, max_new_tokens)

        jit_cache[cache_key] = run

    return run(params, prompt_ids, rng)


def gpt2_tiny(**kw):
    """2L/128d/2h — draft-model config for speculative decoding (and fast
    tests). No reference counterpart: it exists to run a cheap stand-in
    decode whose proposals the serving engine verifies against the real
    model (serving/spec_decode.DraftModelDrafter), so it must share the
    target's vocab; pass ``vocab_size=``/``max_len=`` to match."""
    return GPT2(num_layers=2, d_model=128, num_heads=2, **kw)


def gpt2_small(**kw):
    """12L/768d/12h (parity: example_models.cpp:384-391)."""
    return GPT2(num_layers=12, d_model=768, num_heads=12, **kw)


def gpt2_small_hd128(**kw):
    """12L/768d/6h — GPT-2 small geometry with 128-wide heads.

    TPU-first variant: every attention matmul at head_dim 64 leaves half the
    128-wide MXU idle (not measured since the builders' rooflines of
    2026-07-30: no cell runs this geometry); 6 heads of D=128 keep the
    same d_model/params but run the QK^T/PV contractions at full width. No
    reference counterpart — the reference's head_dim is fixed by the GPT-2
    checkpoint (example_models.cpp:384); this exists for from-scratch
    training where the geometry is free."""
    return GPT2(num_layers=12, d_model=768, num_heads=6, **kw)


def gpt2_small_gqa4(**kw):
    """12L/768d/12h with 4 KV heads (grouped-query attention, beyond
    reference): the decode KV cache — the bandwidth floor of cached decode —
    shrinks 3x, and the flash kernel shares each kv block across its query
    group with zero materialization (ops/pallas/flash_attention.py)."""
    return GPT2(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4, **kw)


def gpt2_medium(**kw):
    """24L/1024d/16h (parity: example_models.cpp:432)."""
    return GPT2(num_layers=24, d_model=1024, num_heads=16, **kw)


def gpt2_large(**kw):
    """36L/1280d/20h (parity: example_models.cpp:480)."""
    return GPT2(num_layers=36, d_model=1280, num_heads=20, **kw)
