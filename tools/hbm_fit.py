#!/usr/bin/env python
"""HBM-fit table for the GPT-2 + Llama families on one chip (VERDICT r03 #6).

Computes EXACT train-state bytes via jax.eval_shape (params + optimizer
moments + BatchNorm-style state; no device memory touched) and bounds the
training activation footprint under remat (per-block boundary activations +
one block's interior). Decode rows: bf16 vs int8 weight bytes + KV cache.

    JAX_PLATFORMS=cpu python -m tools.hbm_fit [--seq 1024] [--hbm-gb 16]
"""
import argparse

import jax
import jax.numpy as jnp


def tree_bytes(t) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))


def row(size: str, batch: int, seq: int):
    from tnn_tpu import models, nn
    from tnn_tpu.train.step import create_train_state

    # a size starting with "llama" names the Llama family directly, anything
    # else is gpt2_<size>
    name = size if size.startswith("llama") else f"gpt2_{size}"
    model = models.create(name, max_len=seq)
    opt = nn.AdamW(lr=1e-4)
    state = jax.eval_shape(
        lambda rng: create_train_state(model, opt, rng, (batch, seq)),
        jax.random.PRNGKey(0))
    state_b = tree_bytes(state)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    d, L = model.d_model, model.num_layers
    # remat: keep block-boundary activations (L+1 of them, bf16) + recompute
    # one block's interior during backward (~6 activation-sized tensors for
    # ln/qkv/attn/mlp) + grads-in-flight ~ params f32
    boundary = (L + 1) * batch * seq * d * 2
    interior = 6 * batch * seq * 4 * d * 2
    grads = 4 * n_params
    logits = batch * seq * model.vocab_size * 4
    train_total = state_b + boundary + interior + grads + logits
    # decode at bs=1: weights (bf16 / int8+wte-scales) + KV cache bf16
    w_bf16 = 2 * n_params
    # 0.52 is the measured int8-vs-bf16 BYTES ratio for GPT-2 (test_quant:
    # int8 matmul weights + bf16-kept embeddings/norms), applied to bytes
    w_int8 = int(w_bf16 * 0.52)
    # GQA models carry H_kv/H of the kv width per position
    kv_frac = getattr(model, "num_kv_heads", model.num_heads) / model.num_heads
    kv = int(2 * L * seq * d * 2 * kv_frac)
    return {"size": name, "params_M": round(n_params / 1e6),
            "train_batch": batch,
            "train_state_GB": round(state_b / 2**30, 2),
            "train_total_GB": round(train_total / 2**30, 2),
            "decode_bf16_GB": round((w_bf16 + kv) / 2**30, 2),
            "decode_int8_GB": round((w_int8 + kv) / 2**30, 2)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-chip HBM (v5e: 16)")
    args = ap.parse_args(argv)
    rows = [row("small", 8, args.seq), row("medium", 4, args.seq),
            row("large", 1, args.seq), row("llama_small", 8, args.seq),
            row("llama_1b", 2, args.seq)]
    cols = list(rows[0])
    print(" | ".join(cols))
    for r in rows:
        fit = "FITS" if r["train_total_GB"] < args.hbm_gb else \
            "NEEDS FSDP/smaller bs"
        print(" | ".join(str(r[c]) for c in cols), "|", fit,
              f"(vs {args.hbm_gb} GB)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
