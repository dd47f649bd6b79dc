#!/usr/bin/env python
"""GPT-2 autoregressive generation (parity: examples/gpt2_inference.cpp:19-122).

    python -m tnn_tpu.cli.gpt2_inference --vocab data/vocab.bin \
        --model-file snapshots/gpt2.tnn --prompt "The meaning of life is" -n 50

Differences from the reference loop: a jit-compiled KV-cache decode (the reference
recomputes the full sequence per token) and sampling temperature. Without
--model-file it runs a randomly initialized gpt2_small — useful as a smoke test
and a tokens/sec benchmark of the decode path itself.
"""
import argparse
import time


import jax
import numpy as np

from tnn_tpu import checkpoint as ckpt_lib
from tnn_tpu import models
from tnn_tpu.data.tokenizer import Tokenizer
from tnn_tpu.models.gpt2 import generate
from tnn_tpu.utils import compile_cache


from tnn_tpu.cli import console_entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="gpt2_small",
                    help="zoo name (used when --model-file is absent)")
    ap.add_argument("--model-file", default="", help=".tnn snapshot")
    ap.add_argument("--vocab", default="", help="vocab.bin (reference format)")
    ap.add_argument("--prompt", default="The meaning of life is")
    ap.add_argument("-n", "--max-new-tokens", type=int, default=50)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample only from the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling: smallest token set with "
                         "cumulative prob >= p (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 decode (in-VMEM-dequant Pallas "
                         "matmul; ~2x fewer weight bytes per token)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if (args.top_k or args.top_p) and args.temperature <= 0:
        # top-k/top-p only shape a STOCHASTIC distribution; under greedy
        # (temperature 0) they would be silently ignored
        print("--top-k/--top-p need sampling: defaulting --temperature 1.0")
        args.temperature = 1.0

    tokenizer = None
    if args.vocab:
        tokenizer = Tokenizer().load(args.vocab)

    if args.model_file:
        model, variables = ckpt_lib.load_model(args.model_file)
        params = variables["params"]
    else:
        model = models.create(args.model)
        print(f"no --model-file: random-weight {args.model} (smoke/benchmark mode)")
        variables = model.init(jax.random.PRNGKey(args.seed), (1, 8))
        params = variables["params"]

    if args.int8:
        from tnn_tpu.nn.quant import quantize_for_decode, quantized_bytes

        before = quantized_bytes(params)
        params = quantize_for_decode(params)
        print(f"int8 weights: {before / 2**20:.0f} MB -> "
              f"{quantized_bytes(params) / 2**20:.0f} MB")

    if tokenizer is not None:
        prompt_ids = np.asarray(tokenizer.encode(args.prompt), np.int32)[None]
    else:
        print("no --vocab: using byte-level prompt ids")
        prompt_ids = np.frombuffer(args.prompt.encode(), np.uint8).astype(
            np.int32)[None] % model.vocab_size

    # generate twice: first call compiles, second measures steady-state decode.
    # np.asarray forces completion — without it the device would still be
    # running the first call when the timer starts.
    kw = dict(temperature=args.temperature, top_k=args.top_k,
              top_p=args.top_p, rng=jax.random.PRNGKey(args.seed))
    out = generate(model, params, prompt_ids, args.max_new_tokens, **kw)
    np.asarray(out)
    t0 = time.perf_counter()
    out = generate(model, params, prompt_ids, args.max_new_tokens, **kw)
    new_tokens = np.asarray(out)[0]  # generate returns only the new tokens
    dt = time.perf_counter() - t0

    if tokenizer is not None:
        full = prompt_ids[0].tolist() + new_tokens.tolist()
        print("---\n" + tokenizer.decode(full) + "\n---")
    else:
        print("generated ids:", new_tokens[:16].tolist(), "...")
    print(f"{len(new_tokens)} tokens in {dt * 1e3:.0f} ms "
          f"({len(new_tokens) / dt:.1f} tok/s)")


cli = console_entry(main)


if __name__ == "__main__":
    main()
