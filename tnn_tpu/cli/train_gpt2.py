#!/usr/bin/env python
"""Train a decoder LM (GPT-2 or Llama family, --arch) on a real token stream.

Parity-and-beyond: the reference trains its conv models but only INFERS with
GPT-2 (examples/gpt2_inference.cpp); this drives the full LM training loop —
mmap token stream -> (B, S) windows -> compiled train step (optionally the
Pallas flash-attention backend) -> held-out perplexity -> KV-cache sampling.

    python -m tnn_tpu.cli.prepare_corpus --out data/pytok --source /usr/lib/python3.12
    python -m tnn_tpu.cli.train_gpt2 --tokens data/pytok --steps 300 --backend xla

Results (loss curve, final train/val perplexity, tok/s) are written as one
JSON file under --results.
"""
import argparse
import json
import os
import time


import jax
import jax.numpy as jnp
import numpy as np

from tnn_tpu import nn
from tnn_tpu.data.token_stream import TokenStreamDataLoader
from tnn_tpu.models.gpt2 import GPT2, generate
from tnn_tpu.train import create_train_state, make_train_step
from tnn_tpu.utils import compile_cache


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tokens", required=True,
                    help="corpus dir from prepare_corpus.py (train.bin/val.bin)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: KV heads (< --heads, "
                         "divisor); 0 = full MHA")
    ap.add_argument("--arch", default="gpt2", choices=["gpt2", "llama"],
                    help="decoder family: gpt2 (learned positions, GELU MLP) "
                         "or llama (RoPE + RMSNorm + SwiGLU)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"],
                    help="attention backend (pallas = the flash kernel)")
    ap.add_argument("--sample", type=int, default=128,
                    help="tokens to sample after training (0 = skip)")
    ap.add_argument("--fused-head-loss", type=int, default=0, metavar="CHUNK",
                    help="vocab chunk for the streaming LM-head loss "
                         "(nn.lm_loss) — 0 uses the materialized-logits path")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="optimizer steps per compiled dispatch (lax.scan); "
                         ">1 amortizes the host->device round trip that "
                         "dominates small models (a non-"
                         "divisor remainder runs as one final smaller "
                         "dispatch, so --steps is always exact)")
    ap.add_argument("--results", default="logs/train_gpt2")
    args = ap.parse_args(argv)
    compile_cache.enable()

    meta = json.load(open(os.path.join(args.tokens, "meta.json")))
    vocab = int(meta["vocab_size"])
    train_loader = TokenStreamDataLoader(
        os.path.join(args.tokens, "train.bin"), args.seq)
    val_path = os.path.join(args.tokens, "val.bin")
    val_loader = TokenStreamDataLoader(val_path, args.seq) \
        if os.path.exists(val_path) else None
    print(f"corpus: {meta['train_tokens']} train tokens, vocab {vocab}")

    # dispatch granularity first: total_steps feeds the scheduler horizon.
    # A non-divisor remainder folds into one final smaller dispatch, so
    # --steps 200 runs exactly 200 optimizer steps at any --steps-per-call.
    spc = max(1, min(args.steps_per_call, args.steps))
    n_full, rem = divmod(args.steps, spc)
    total_steps = args.steps
    call_sizes = [spc] * n_full + ([rem] if rem else [])
    if rem:
        print(f"note: {n_full} dispatches x {spc} steps + one {rem}-step "
              "remainder dispatch (exact --steps)")

    model_kw = dict(vocab_size=vocab, max_len=args.seq,
                    num_layers=args.layers, d_model=args.d_model,
                    num_heads=args.heads, backend=args.backend,
                    num_kv_heads=args.kv_heads or None)
    if args.arch == "llama":
        from tnn_tpu.models.llama import Llama

        model = Llama(**model_kw)
    else:
        model = GPT2(dropout=0.0, **model_kw)
    opt = nn.AdamW(lr=args.lr, weight_decay=0.01, grad_clip_norm=1.0)
    sched = nn.WarmupCosineAnnealing(warmup=max(10, total_steps // 20),
                                     t_max=total_steps)
    state = create_train_state(model, opt, jax.random.PRNGKey(0),
                               (args.batch, args.seq))
    def make_step(n):
        return make_train_step(model, opt, scheduler=sched,
                               compute_accuracy=not args.fused_head_loss,
                               lm_head_chunk=args.fused_head_loss or None,
                               steps_per_call=n)

    step = make_step(spc)
    step_rem = make_step(rem) if rem else None

    rng = np.random.default_rng(0)
    curve = []
    done = 0
    t0 = time.time()
    for c, n in enumerate(call_sizes):
        data, labels = train_loader.random_windows(args.batch * n, rng)
        if n > 1:
            data = data.reshape(n, args.batch, args.seq)
            labels = labels.reshape(n, args.batch, args.seq)
        fn = step if n == spc else step_rem
        state, m = fn(state, jnp.asarray(data, jnp.int32),
                      jnp.asarray(labels, jnp.int32))
        done += n
        i = done - 1
        if c % max(1, 20 // spc) == 0 or done == total_steps:
            loss = float(m["loss_trace"][-1]) if n > 1 else float(m["loss"])
            curve.append({"step": i, "loss": round(loss, 4),
                          "ppl": round(float(np.exp(loss)), 3)})
            print(f"step {i}: loss {loss:.4f} ppl {np.exp(loss):.2f}")
    train_secs = time.time() - t0
    tok_s = total_steps * args.batch * args.seq / train_secs

    out = {"metric": f"{args.arch}_bytes_lm", "backend": args.backend,
           # a CPU curve must never masquerade as chip numbers
           "platform": jax.devices()[0].platform,
           "model": {"layers": args.layers, "d_model": args.d_model,
                     "heads": args.heads, "seq": args.seq, "vocab": vocab},
           "steps": total_steps, "steps_per_call": spc,
           "train_tok_per_s": round(tok_s, 1),
           "final_train_loss": curve[-1]["loss"],
           "final_train_ppl": curve[-1]["ppl"], "curve": curve}

    if val_loader is not None:
        from tnn_tpu.train import make_eval_step

        ev = make_eval_step(model, compute_accuracy=False)
        losses = []
        for _ in range(10):
            d, l = val_loader.random_windows(args.batch, rng)
            losses.append(float(ev(state, jnp.asarray(d, jnp.int32),
                                   jnp.asarray(l, jnp.int32))["loss"]))
        val_loss = float(np.mean(losses))
        out["val_loss"] = round(val_loss, 4)
        out["val_ppl"] = round(float(np.exp(val_loss)), 3)
        print(f"held-out: loss {val_loss:.4f} ppl {np.exp(val_loss):.2f}")

    if args.sample > 0 and meta["mode"] == "byte":
        d, _ = val_loader.random_windows(1, rng) if val_loader is not None \
            else train_loader.random_windows(1, rng)
        # prompt + new tokens must fit the context; shrink the prompt (and,
        # at tiny --seq, the sample) rather than erroring out of the run
        args.sample = min(args.sample, args.seq - 1)
        prompt_len = min(32, args.seq - args.sample)
        prompt = jnp.asarray(d[:, :prompt_len], jnp.int32)
        t0 = time.time()
        toks = np.asarray(generate(model, state.params, prompt, args.sample,
                                   temperature=0.8, max_len=args.seq))
        decode_s = time.time() - t0
        text = bytes(int(t) for t in toks[0] if t < 256).decode(
            "utf-8", errors="replace")
        out["decode_tok_per_s"] = round(args.sample / decode_s, 1)
        out["sample"] = text[:200]
        print(f"sample ({out['decode_tok_per_s']} tok/s incl compile):")
        print(text[:200])

    os.makedirs(args.results, exist_ok=True)
    path = os.path.join(args.results,
                        f"lm_{args.arch}_{meta['mode']}_{args.backend}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print("results ->", path)
    return out


cli = console_entry(main)


if __name__ == "__main__":
    main()
