#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU; nothing else

One process drives the two main paths once through the entry points a user
would call — ``tnn-serve`` (``tnn_tpu.cli.serve.main``, stdin JSON lines) on
gpt2_small at its full width and ``tnn-trainer`` (``tnn_tpu.cli.trainer.main``)
on cifar100_wrn16_8 — with random weights made from a seed, and checks what
comes out by the repo's own means. Phases, each printing its name and result:

  device     platform / device_kind / count as JAX reports them, versions,
             whether the native host library built, where the compile cache
             lives, and whether ``block_until_ready`` blocks here.
  kernel     ``paged_attention(backend="pallas", interpret=False)`` against
             the XLA gather reference at gpt2_small geometry: decode form,
             chunk widths 8 and 64, spec width 5, bf16 pages (plain, and two
             heads a page row as the pool holds them) and int8 pages, stats
             on and off, -1-holed tables. Max abs error per row of the table.
             Then the other kernels at their cells' widths against their XLA
             forms, among them the flash kernels at the training cell's call
             (forward, and forward + backward) with their sub-tile plan, and
             the KV row write at the six serving configurations' pages
             against the whole-page form, bit for bit, in a donated pool,
             and ``tnn_eva_attention`` at the EvaByte cell's shape (decode
             form and a chunk of 256) with the time a launch.
  serve      nine token-id requests (prompts of 5..700 tokens, two sharing a
             96-token prefix) through ``tnn-serve --model gpt2_small
             --num-blocks 512 --block-size 16 --max-batch-size 8``, every
             other flag at its default. The engine isolates step failures by
             design, so the phase looks through it: every request must end
             ``done`` with exactly 32 in-vocabulary tokens, the summary must
             show no failure, restart or retry and a prefix-cache hit, the
             engine must have stayed on the paged path with compiled (not
             interpreted) kernels and its pool on a TPU device — and every
             generated token must be within LOGIT_TOL of the arg-max of a
             plain full-sequence ``model.apply`` forward on the same chip
             (random weights make greedy tokens tie-prone, so logits are
             compared, not token ids). For two prompts the model's paged
             path (chunked ``apply_paged`` over a pool) is also compared
             logit-for-logit with that forward.
  train      ``tnn-trainer --model cifar100_wrn16_8 --dataset synthetic
             --num-classes 100 --batch-size 256 --epochs 1`` (50 steps +
             validation): finite loss every step, step count, parameters
             moved. img/s is printed as information, not as a benchmark.
  four_chip  with >= 4 devices: serve again with ``--tp 4`` and ``--sp 4``
             (same checks, state on all four devices), ``tnn-trainer --mesh
             data=4``, and ``--replicas 4`` with replica i's params and pool
             on device i. Otherwise ``skipped: needs 4 devices, found N``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed. With no TPU, or away from the
repository it drives, the script exits 2 and prints no result; any failed
phase exits 1.

``--rehearse`` runs the same control flow on whatever backend JAX has (set
``JAX_PLATFORMS=cpu`` yourself), with a toy model and interpreted kernels, to
debug this script in a sandbox without a chip. It says so on every line it can
and never prints the ``"ok"`` result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import functools
import io
import json
import os
import re
import sys
import tempfile
import threading
import time
import traceback
from unittest import mock

PHASES = ("device", "kernel", "serve", "train", "four_chip")

# Tolerances, stated before the chip was asked. bf16 has 8 bits of mantissa
# (eps 2^-8 = 3.9e-3); attention outputs are O(1) averages of unit-variance
# values and both sides accumulate in f32, so a few eps bounds the difference.
KERNEL_TOL = {"bf16": 2e-2, "pack2": 2e-2, "int8": 3e-2}
# gpt2_small's random-init logits have a spread of ~0.5 and a top-1/top-2 gap
# of a few hundredths; a token drawn from a WRONG distribution sits ~2 below
# the arg-max. 0.25 separates "bf16 reordering moved a near-tie" from "wrong".
LOGIT_TOL = 0.25

CHIP = dict(
    model="gpt2_small", num_blocks=512, block_size=16, max_batch=8,
    max_new=32, prompt_lens=(5, 17, 64, 100, 200, 333, 500), prefix_len=96,
    sharer_lens=(700, 130),
    kernel=dict(layers=2, blocks=512, heads=12, kv_heads=12, block_size=16,
                head_dim=64, batch=8, table=64),
    # the latent kernel and the grouped expert product at Mistral Small 4's
    # widths: pages of 128 rows of 384, 32 heads; experts of 2,048 x 4,096
    latent=dict(blocks=256, block_size=128, row=384, value=256, heads=32,
                batch=8, table=24, chunk=64),
    experts=dict(held=8, hidden=2048, width=4096, tokens=(32, 2048)),
    # ... and at LongCat-Flash's: rows of 640 (value 512) under 64 heads, the
    # decode form; 16 held experts of 2,048 x 6,144 at a step's 64 rows
    latent_wide=dict(blocks=64, block_size=128, row=640, value=512, heads=64,
                     batch=8, table=8, chunk=None),
    experts_wide=dict(held=16, hidden=2048, width=6144, tokens=(64,)),
    # the paged kernel under a sliding window at Trinity Large's widths: 48
    # query heads over 8 KV heads of 128, pages of 128, a window of 4,096
    # read from a table that lists only the pages a row still holds
    window=dict(blocks=288, block_size=128, heads=48, kv_heads=8,
                head_dim=128, batch=8, window=4096, chunk=64),
    # the paged kernel at GPT-2 large's packed shape, 36 layers a call: what
    # a decode row costs in a launch as wide as a prompt chunk
    short_rows=dict(layers=36, blocks=1024, heads=20, block_size=16,
                    head_dim=64, batch=16, table=64, chunk=64, iters=10),
    # the gated delta rule's step at Qwen3-Next's cell: 96 rows of 32 value
    # heads' states of 128 x 128 float32 in 97 slots of 2 layers, a snapshot
    # at every third row; and the paged kernel at that model's full layers:
    # 16 query heads over 2 KV heads of 256, pages of 128
    state=dict(layers=2, rows=96, heads=32, key=128, value=128),
    heads256=dict(blocks=160, block_size=128, heads=16, kv_heads=2,
                  head_dim=256, batch=8, table=16, chunk=32),
    # the Mamba-2 step at granite-4.0-h-micro's cell: 24 rows of 64 heads'
    # states of 64 x 128 float32 in 25 slots of 2 layers, a snapshot at every
    # third row; and the paged kernel at that model's attention layers: 32
    # query heads over 8 KV heads of 64, two heads a page row, pages of 128
    ssm=dict(layers=2, rows=24, heads=64, head_dim=64, state=128),
    heads64=dict(blocks=160, block_size=128, heads=32, kv_heads=8,
                 head_dim=64, batch=8, table=16, chunk=64),
    # the flash kernels at gpt2-medium.train's call: batch 8, 16 heads of
    # 64 over 1,024 causal positions
    flash=dict(batch=8, heads=16, seq=1024, head_dim=64, iters=10),
    # the EVA kernel at evabyte-pp2.decode-docs' shape: 8 rows of 32 heads of
    # 128 over pages of 128, a window of 16 exact pages beside 16 summary
    # pages, the decode form and a prompt chunk of 256 (2 layers of the 16)
    eva=dict(layers=2, blocks=224, heads=32, block_size=128, head_dim=128,
             batch=8, window=2048, summaries=2048, chunk=256, iters=20),
    # the row write at the six serving configurations' pages, (page rows of
    # a position, positions a page, lanes a row): a step's rows and one
    # prompt chunk beside them, into a donated pool of 2 layers
    row_write=dict(pages={"gpt2-large": (10, 16, 128),
                          "evabyte": (32, 128, 128),
                          "trinity-large": (8, 128, 128),
                          "qwen3-next": (2, 128, 256),
                          "mistral-small4": (1, 128, 384),
                          "longcat-flash": (1, 128, 640)},
                   batch=16, table=3, chunk=64, iters=20),
    # the sampler at the two served vocabularies: GPT-2 large's 16 rows, the
    # 32 rows of Mistral Small 4's slice
    sampler=dict(shapes=((16, 50257), (32, 32768)), iters=50),
    train_model="cifar100_wrn16_8", train_batch=256, train_classes=100,
    degree=4, mesh_steps=5)
REHEARSAL = dict(
    model="gpt2_tiny", num_blocks=64, block_size=16, max_batch=4,
    max_new=4, prompt_lens=(5, 17, 40), prefix_len=32, sharer_lens=(70, 45),
    kernel=dict(layers=1, blocks=16, heads=2, kv_heads=2, block_size=16,
                head_dim=64, batch=3, table=8),
    latent=dict(blocks=16, block_size=8, row=128, value=32, heads=4,
                batch=3, table=6, chunk=8),
    experts=dict(held=4, hidden=256, width=128, tokens=(8, 64)),
    latent_wide=dict(blocks=16, block_size=8, row=256, value=128, heads=8,
                     batch=3, table=6, chunk=None),
    experts_wide=dict(held=6, hidden=128, width=256, tokens=(8,)),
    window=dict(blocks=32, block_size=8, heads=4, kv_heads=2, head_dim=32,
                batch=3, window=16, chunk=8),
    short_rows=dict(layers=1, blocks=32, heads=4, block_size=16, head_dim=64,
                    batch=3, table=8, chunk=16, iters=1),
    state=dict(layers=2, rows=3, heads=8, key=16, value=16),
    heads256=dict(blocks=16, block_size=8, heads=4, kv_heads=2, head_dim=32,
                  batch=3, table=6, chunk=8),
    ssm=dict(layers=2, rows=3, heads=8, head_dim=16, state=128),
    heads64=dict(blocks=16, block_size=8, heads=8, kv_heads=2, head_dim=64,
                 batch=3, table=6, chunk=8),
    flash=dict(batch=1, heads=2, seq=64, head_dim=32, iters=1),
    eva=dict(layers=1, blocks=32, heads=4, block_size=8, head_dim=32,
             batch=3, window=32, summaries=24, chunk=8, iters=1),
    row_write=dict(pages={"packed": (2, 16, 128), "latent": (1, 32, 256)},
                   batch=3, table=3, chunk=64, iters=1),
    sampler=dict(shapes=((4, 320),), iters=2),
    train_model="mnist_cnn", train_batch=8, train_classes=10,
    degree=2, mesh_steps=2)


def log(msg: str = "") -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- device ----

def phase_device(cfg) -> list:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp

    from tnn_tpu import native
    from tnn_tpu.utils import compile_cache
    from tnn_tpu.utils.hardware import device_line

    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            vers[pkg] = "absent"
    log(device_line() + " " + " ".join(f"{k}={v}" for k, v in vers.items()))
    log(f"native: {'built' if native.available() else 'python fallback'}")
    cache_dir = compile_cache.enable()
    log(f"compile cache: {compile_cache.describe(cache_dir)}"
        + (f" [{compile_cache.ENV_VAR} set]"
           if os.environ.get(compile_cache.ENV_VAR) else ""))
    cfg["cache_dir"] = cache_dir

    # does block_until_ready block? Queue a chain of matmuls, time the wait,
    # then time a value fetch: if the wait really waited, the fetch is free.
    n, reps = (4096, 50) if not cfg["rehearse"] else (128, 4)
    step = jax.jit(lambda a: (a @ a) * (1.0 / n))
    corner = jax.jit(lambda a: a[0, 0].astype(jnp.float32))
    x = jnp.ones((n, n), jnp.bfloat16)
    float(corner(step(x)))      # compile both before the clock starts
    t0 = time.perf_counter()
    y = x
    for _ in range(reps):
        y = step(y)
    t_enq = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_bur = time.perf_counter() - t0
    float(corner(y))
    t_fetch = time.perf_counter() - t0 - t_bur
    log(f"block_until_ready: enqueue {t_enq * 1e3:.1f} ms, wait "
        f"{(t_bur - t_enq) * 1e3:.1f} ms, fetch after it {t_fetch * 1e3:.2f} ms "
        f"-> {'blocks' if t_bur - t_enq > t_fetch else 'DOES NOT BLOCK'} "
        "(informational)")
    return []


# ---------------------------------------------------------------- kernel ----

def phase_kernel(cfg) -> list:
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas.paged_attention import (QuantPages,
                                                    paged_attention,
                                                    quantize_kv_rows)

    k = cfg["kernel"]
    L, N, H, Hkv = k["layers"], k["blocks"], k["heads"], k["kv_heads"]
    bs, dh, B, nb = k["block_size"], k["head_dim"], k["batch"], k["table"]
    interpret = cfg["rehearse"]
    rng = np.random.default_rng(0)

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    pk, pv = rand((L, N, Hkv, bs, dh)), rand((L, N, Hkv, bs, dh))

    def pack2(x):   # two heads side by side in a page row, as the pool
        # holds heads of 64 (``PagedKVPool.page_shape``)
        return x.reshape(L, N, Hkv // 2, 2, bs, dh).swapaxes(3, 4) \
            .reshape(L, N, Hkv // 2, bs, 2 * dh)

    # name -> the pages the kernel reads, the pages the XLA path reads
    pages = {"bf16": ((pk, pv),) * 2,
             "pack2": ((pack2(pk), pack2(pv)), (pk, pv)),
             "int8": ((QuantPages(*quantize_kv_rows(pk)),
                       QuantPages(*quantize_kv_rows(pv))),) * 2}
    cap = nb * bs
    kv_lens = np.array([1, bs, bs + 1, 100, cap // 2 - 1, 700, cap - 24,
                        cap])[:B].clip(1, cap).astype(np.int32)
    tables = rng.integers(1, N, (B, nb)).astype(np.int32)
    # the table a 2-way sequence-parallel shard sees: every other page is
    # another shard's (-1); their positions must be skipped, not read
    holed = np.where(np.arange(nb)[None, :] % 2 == 1, -1, tables)

    forms = [("decode", None), ("chunk8", 8), ("chunk64", 64), ("spec5", 5)]
    failures = []
    log(f"geometry: pages (L={L}, N={N}, H_kv={Hkv}, bs={bs}, Dh={dh}), "
        f"q heads {H}, batch {B}, tables {nb} wide, interpret={interpret}")
    log(f"{'form':8s} {'pages':5s} {'stats':5s} {'holes':5s} "
        f"{'max|out|err':>11s} {'max|m|err':>10s} {'max l relerr':>12s} "
        f"{'tol':>6s}")
    for pname, ((pgk, pgv), (rfk, rfv)) in pages.items():
        tol = KERNEL_TOL[pname]
        for fname, qw in forms:
            for stats in (False, True):
                for tname, tbl in (("no", tables), ("yes", holed)):
                    if tname == "yes" and not stats:
                        continue   # holes only occur with the SP merge
                    q = rand((B, H, dh) if qw is None else (B, qw, H, dh))
                    q_lens = None if qw is None else jnp.asarray(
                        np.minimum(np.array([qw, 1, qw // 2 + 1] * B)[:B],
                                   kv_lens), jnp.int32)
                    kw = dict(q_lens=q_lens, layer=L - 1,
                              return_stats=stats)
                    got = paged_attention(
                        q, pgk, pgv, jnp.asarray(tbl), jnp.asarray(kv_lens),
                        backend="pallas", interpret=interpret, **kw)
                    want = paged_attention(
                        q, rfk, rfv, jnp.asarray(tbl), jnp.asarray(kv_lens),
                        backend="xla", **kw)
                    got, want = (got, want) if stats else ((got,), (want,))
                    g = [np.asarray(a, np.float32) for a in got]
                    w = [np.asarray(a, np.float32) for a in want]
                    e_out = float(np.max(np.abs(g[0] - w[0])))
                    e_m = e_l = 0.0
                    if stats:
                        live = w[2] > 0        # rows that attended anything
                        e_m = float(np.max(np.abs(g[1] - w[1]) * live))
                        e_l = float(np.max(np.abs(g[2] - w[2])
                                           / np.maximum(w[2], 1.0)))
                    ok = all(np.isfinite(a).all() for a in g) \
                        and max(e_out, e_m, e_l) <= tol
                    log(f"{fname:8s} {pname:5s} {'on' if stats else 'off':5s} "
                        f"{tname:5s} {e_out:11.2e} {e_m:10.2e} {e_l:12.2e} "
                        f"{tol:6.0e} {'ok' if ok else 'FAIL'}")
                    if not ok:
                        failures.append(
                            f"kernel {fname}/{pname}/stats={stats}/"
                            f"holes={tname}: error above {tol}")
    return failures + _latent_and_expert_kernels(cfg, rand, rng) \
        + _window_kernel(cfg, rand, rng) + _state_kernels(cfg, rand, rng) \
        + _short_rows(cfg, rand, rng) + _row_writes(cfg, rand, rng) \
        + _flash_training_call(cfg, rand) + _eva_kernel(cfg, rand, rng) \
        + _sampler_steps(cfg, rng)


def _state_kernels(cfg, rand, rng) -> list:
    """``tnn_gdn_step`` and ``tnn_mamba2_step`` against their ``jax.numpy``
    steps at a decode step's shapes (the output, every live state written,
    and the snapshot of the rows that keep one); and the paged kernel at
    heads of 256 (2 KV heads, 8 query heads each) and at grouped queries over
    PACKED heads of 64 (two KV heads a page row, 4 query heads each), which
    no other model runs it at."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas.gdn_step import gdn_step
    from tnn_tpu.ops.pallas.mamba2_step import mamba2_step
    from tnn_tpu.ops.pallas.paged_attention import lane_pack, paged_attention

    interpret, failures = cfg["rehearse"], []

    check = functools.partial(_check_close, failures)

    k = cfg["state"]
    L, B, H, dk, dv = k["layers"], k["rows"], k["heads"], k["key"], k["value"]

    def f32(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, kk, v = unit(f32((B, H, dk))) * dk ** -0.5, unit(f32((B, H, dk))), \
        f32((B, H, dv))
    g, beta = -jnp.abs(f32((B, H))), jax.nn.sigmoid(f32((B, H)))
    rec, snap = f32((L, B + 1, H, dk, dv)), jnp.zeros(
        (L, 2 * B + 1, H, dk, dv), jnp.float32)
    slots = jnp.asarray(rng.permutation(B) + 1, jnp.int32)
    snaps = jnp.where(jnp.arange(B) % 3 == 0, 2 * (slots - 1) + 1, 0)
    args = (q, kk, v, g, beta, rec, snap, slots, snaps)
    want = gdn_step(*args, layer=1, backend="xla")
    got = gdn_step(*args, layer=1, backend="pallas", interpret=interpret)
    name = f"gdn_step {B} rows x {H} heads"
    check(name + " out", got[0], want[0], 1e-4)
    check(name + " states", got[1][:, 1:], want[1][:, 1:], 1e-4)
    check(name + " snapshots", got[2][:, 1:], want[2][:, 1:], 0.0)

    k = cfg["ssm"]
    L, B, H, P, N = (k["layers"], k["rows"], k["heads"], k["head_dim"],
                     k["state"])
    step = jax.nn.softplus(f32((B, H)) - 3.0)
    rec, snap = f32((L, B + 1, H, P, N)), jnp.zeros(
        (L, 2 * B + 1, H, P, N), jnp.float32)
    slots = jnp.asarray(rng.permutation(B) + 1, jnp.int32)
    snaps = jnp.where(jnp.arange(B) % 3 == 0, 2 * (slots - 1) + 1, 0)
    args = (f32((B, H, P)), step, -8.0 * step, f32((B, N)), f32((B, N)), rec,
            snap, slots, snaps)
    want = mamba2_step(*args, layer=1, backend="xla")
    got = mamba2_step(*args, layer=1, backend="pallas", interpret=interpret)
    name = f"mamba2_step {B} rows x {H} heads"
    check(name + " out", got[0], want[0], 1e-4)
    check(name + " states", got[1][:, 1:], want[1][:, 1:], 1e-4)
    check(name + " snapshots", got[2][:, 1:], want[2][:, 1:], 0.0)

    for k, what, more in (
            (cfg["heads256"], "16 x 256 over 2", dict(group_positions=512)),
            (cfg["heads64"], "32 x 64 over 8 packed", {})):
        bs, B, nb = k["block_size"], k["batch"], k["table"]
        pack = lane_pack(k["kv_heads"], k["head_dim"], jnp.bfloat16)
        pk, pv = (rand((2, k["blocks"], k["kv_heads"] // pack, bs,
                        pack * k["head_dim"])) for _ in range(2))
        tables = jnp.asarray(rng.integers(1, k["blocks"], (B, nb)), jnp.int32)
        cap = nb * bs
        for fname, qw in (("decode", 1), (f"chunk{k['chunk']}", k["chunk"])):
            kv_lens = np.array([qw, bs, bs + 1, cap // 3, cap // 2 - 1,
                                cap - bs - 5, cap - 24, cap])[:B].clip(
                                    qw, cap).astype(np.int32)
            q_lens = np.minimum(np.array([qw, 1, qw // 2 + 1] * B)[:B],
                                kv_lens)
            qq = rand((B, qw, k["heads"], k["head_dim"]))
            kw = dict(q_lens=jnp.asarray(q_lens, jnp.int32), layer=1)
            a = (qq, pk, pv, tables, jnp.asarray(kv_lens))
            check(f"paged {fname} {what}",
                  paged_attention(*a, backend="pallas", interpret=interpret,
                                  **more, **kw),
                  paged_attention(*a, backend="xla", **kw),
                  KERNEL_TOL["bf16"])
    return failures


def _best_of_three(call, iters) -> float:
    """ms a ``call()``: the best of three times ``iters`` calls, each time
    from the host around ``block_until_ready`` of the last."""
    import jax

    took = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            last = call()
        jax.block_until_ready(last)
        took.append((time.perf_counter() - t0) / iters * 1e3)
    return min(took)


def _short_rows(cfg, rand, rng) -> list:
    """What a short row costs in a wide launch: the paged kernel alone over
    every layer of a pool of two heads a page row, all rows but one decoding
    beside ONE prompt chunk, timed from the host around
    ``block_until_ready`` against the same launch with every row a full
    chunk and against the decode form, whose rows the wide launch's decode
    rows must equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas.paged_attention import paged_attention

    k = cfg["short_rows"]
    L, N, H, bs, dh = (k["layers"], k["blocks"], k["heads"],
                       k["block_size"], k["head_dim"])
    B, nb, qw = k["batch"], k["table"], k["chunk"]
    pk, pv = (rand((L, N, H // 2, bs, 2 * dh)) for _ in range(2))
    kv_lens = rng.integers(qw + 1, nb * bs * 4 // 5, B).astype(np.int32)
    tables = jnp.asarray(rng.integers(1, N, (B, nb)), jnp.int32)
    q = rand((B, qw, H, dh))

    def launch(q_lens):
        ql = None if q_lens is None else jnp.asarray(q_lens, jnp.int32)

        @jax.jit
        def every_layer(q, pk, pv):
            return sum(paged_attention(
                q, pk, pv, tables, jnp.asarray(kv_lens), q_lens=ql, layer=i,
                backend="pallas", interpret=cfg["rehearse"]
            ).astype(jnp.float32) for i in range(L))

        x = q if q_lens is not None else q[:, 0]
        out = np.asarray(every_layer(x, pk, pv))           # compiles
        return out, _best_of_three(lambda: every_layer(x, pk, pv),
                                   k["iters"])

    decode, t_decode = launch(None)
    short, t_short = launch([1] * (B - 1) + [qw])
    _, t_full = launch([qw] * B)
    name = f"paged mixed {B - 1} x 1 + 1 x {qw}"
    log(f"{name}: {t_short:.3f} ms a {L} layers, against paged mixed "
        f"{B} x {qw}: {t_full:.3f} ms")
    log(f"{name}: {t_short:.3f} ms a {L} layers, against the decode form "
        f"{B} x 1: {t_decode:.3f} ms (host clock, the best of three times "
        f"{k['iters']} calls each)")
    tol = L * KERNEL_TOL["pack2"]
    err = float(np.max(np.abs(short[:B - 1, 0] - decode[:B - 1])))
    dead = float(np.max(np.abs(short[:B - 1, 1:])))
    if not (err <= tol and dead == 0.0):
        return [f"kernel {name}: its decode rows differ from the decode "
                f"form's by {err:.2e} (tol {tol:.0e}), dead positions read "
                f"{dead:.2e}"]
    return []


def _row_writes(cfg, rand, rng) -> list:
    """``tnn_kv_row_write`` against the whole-page form at each serving
    configuration's page: a decode step's rows, and a mixed step's (one
    prompt chunk from mid-tile across a page's edge, a row of one position,
    an absent row, the others decoding), bit for bit on every page but the
    scratch page; the pool donated through jit keeps its buffer; and the
    time of one write in each form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas import paged_attention as pa

    k = cfg["row_write"]
    B, chunk = k["batch"], k["chunk"]
    kernel = functools.partial(pa._write_rows_pallas,
                               interpret=cfg["rehearse"])
    failures = []
    for name, (hp, bs, width) in k["pages"].items():
        nb = max(k["table"], (chunk + bs) // bs + 1)    # a chunk fits a table
        shape = (2, 1 + B * nb, hp, bs, width)
        tables = jnp.asarray(1 + rng.permutation(B * nb).reshape(B, nb),
                             jnp.int32)
        for form, qw in (("decode", 1), (f"mixed{chunk}", chunk)):
            starts = rng.integers(0, nb * bs - qw, B).astype(np.int32)
            q_lens = np.ones(B, np.int32)
            if qw > 1:      # the chunk leaves its page in mid-tile
                starts[0], q_lens[0] = bs - 5, qw
                q_lens[1] = 0
            rows = rand((B, qw, hp, width))
            args = (tables, jnp.asarray(starts), rows, jnp.asarray(q_lens),
                    jnp.asarray(1, jnp.int32))
            took, out = {}, {}
            fresh = rand(shape)
            before = np.asarray(fresh, np.float32)
            for label, write in (("pages", pa._write_rows_xla),
                                 ("kernel", kernel)):
                step = jax.jit(write, donate_argnums=(0,))
                pool = fresh + 0
                at = pool.unsafe_buffer_pointer()
                pool = step(pool, *args)                    # compiles
                kept = pool.unsafe_buffer_pointer() == at
                out[label] = np.asarray(pool, np.float32)
                t0 = time.perf_counter()
                for _ in range(k["iters"]):
                    pool = step(pool, *args)
                jax.block_until_ready(pool)
                took[label] = (time.perf_counter() - t0) / k["iters"] * 1e3
                del pool
            same = np.array_equal(out["kernel"][:, 1:], out["pages"][:, 1:])
            wrote = int(np.any(out["kernel"][1] != before[1],
                               axis=(1, 3)).sum())
            want = int(q_lens.sum())
            ok = same and wrote == want and (kept or cfg["rehearse"]) \
                and np.array_equal(out["kernel"][0], before[0])
            log(f"row write {name:15s} {form:8s} page {hp} x {bs} x {width}: "
                f"{wrote} positions written (of {want}), "
                f"{'equal to' if same else 'DIFFERS from'} the page form, "
                f"donated buffer {'kept' if kept else 'NOT kept'}; "
                f"{took['kernel']:.3f} ms a write against "
                f"{took['pages']:.3f} ms in whole pages "
                f"{'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(f"kernel row write {name}/{form}: not the "
                                "page form's bits, or the pool was copied")
    return failures


def _flash_training_call(cfg, rand) -> list:
    """The flash kernels at the training cell's call (causal, no offset, no
    mask): the forward, and the forward + backward through ``jax.grad``,
    against the XLA path, with the sub-tile plan they walk and the ms a call
    (host clock; a mismatch fails, a time never does)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.nn.attention import local_xla_attention
    from tnn_tpu.ops.pallas import flash_attention as fa

    k = cfg["flash"]
    shape = (k["batch"], k["heads"], k["seq"], k["head_dim"])
    q, kk, v, g = (rand(shape) for _ in range(4))

    def grad_of(attn):
        return jax.jit(jax.grad(
            lambda q, kk, v: jnp.sum(attn(q, kk, v, causal=True).astype(
                jnp.float32) * g.astype(jnp.float32)), argnums=(0, 1, 2)))

    calls = {"forward": (jax.jit(functools.partial(
                             fa.flash_attention, causal=True)),
                         jax.jit(functools.partial(
                             local_xla_attention, causal=True))),
             "forward + backward": (grad_of(fa.flash_attention),
                                    grad_of(local_xla_attention))}
    failures = []
    for name, (flash, xla) in calls.items():
        got, want = flash(q, kk, v), xla(q, kk, v)           # compiles
        t0 = time.perf_counter()
        for _ in range(k["iters"]):
            last = flash(q, kk, v)
        jax.block_until_ready(last)
        ms = (time.perf_counter() - t0) / k["iters"] * 1e3
        # a gradient's largest entries are a few units: held as a share of
        # the largest, like the bf16 ulp that bounds it
        err = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            err = max(err, float(np.max(np.abs(a - b))
                                 / max(1.0, np.max(np.abs(b)))))
        ok = err <= KERNEL_TOL["bf16"]
        log(f"flash {name} {shape}: max|err| {err:9.2e} tol "
            f"{KERNEL_TOL['bf16']:6.0e} {'ok' if ok else 'FAIL'}, "
            f"{ms:.3f} ms a call (host clock, {k['iters']} calls)")
        if not ok:
            failures.append(f"kernel flash {name}: error above "
                            f"{KERNEL_TOL['bf16']}")
    s = k["seq"]
    plans = [tuple(fa.causal_tile_plan(s, s, block, block, sub, sub))
             for block, sub in ((fa.DEFAULT_BLOCK_Q, fa.SUB_TILE),
                                (fa.DEFAULT_BLOCK_FUSED_BWD, fa.SUB_TILE))]
    log("flash sub-tiles of a head (unmasked, masked, left out): forward "
        f"{plans[0]}, backward {plans[1]}")
    return failures


def _eva_kernel(cfg, rand, rng) -> list:
    """``tnn_eva_attention`` against its ``jax.numpy`` path at the EvaByte
    cell's shape, the decode form and a prompt chunk: windows half full on
    average, a row at a window's first position, a row with no summaries, a
    full window; and the time a launch (a layer's call), from the host
    around ``block_until_ready`` over every layer of the pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas import eva_attention as eva
    from tnn_tpu.ops.pallas.paged_attention import fetch_group

    k = cfg["eva"]
    L, N, H, bs, dh, B = (k["layers"], k["blocks"], k["heads"],
                          k["block_size"], k["head_dim"], k["batch"])
    W, n_exact, n_sum = (k["window"], k["window"] // bs,
                         k["summaries"] // bs)
    pk, pv = (rand((L, N, H, bs, dh)) for _ in range(2))
    tables = jnp.asarray(rng.integers(1, N, (B, n_exact + n_sum)), jnp.int32)
    # window-relative lengths from a window's first position to its last,
    # summary rows from none to all but a page's
    exact = np.linspace(1, W, B).astype(np.int32)
    sums = np.linspace(k["summaries"] - bs, 0, B).astype(np.int32)
    sums[::2] = sums[::2][::-1]
    failures = []
    for fname, qw in (("decode", 1), (f"chunk{k['chunk']}", k["chunk"])):
        q_lens = np.array([qw, 1, qw // 2 + 1] * B)[:B].astype(np.int32)
        args = (rand((B, qw, H, dh)), pk, pv, tables,
                jnp.asarray(np.maximum(exact, q_lens)), jnp.asarray(sums))
        kw = dict(n_exact=n_exact, q_lens=jnp.asarray(q_lens))
        _check_close(
            failures, f"eva {fname}",
            eva.eva_attention(*args, layer=L - 1, backend="pallas",
                              interpret=cfg["rehearse"], **kw),
            eva.eva_attention(*args, layer=L - 1, backend="xla", **kw),
            KERNEL_TOL["bf16"])

        @jax.jit
        def every_layer(*args):
            return sum(eva.eva_attention(
                *args, layer=i, backend="pallas", interpret=cfg["rehearse"],
                **kw).astype(jnp.float32) for i in range(L))

        jax.block_until_ready(every_layer(*args))           # compiles
        took = _best_of_three(lambda: every_layer(*args), k["iters"]) / L
        pages, heads = fetch_group(
            bs=bs, dh=dh, hkv=H, qg=qw, page_dtype=pk.dtype,
            nb=n_exact + n_sum, positions=eva.GROUP_POSITIONS)
        log(f"eva {fname} {B} rows x {H} heads of {dh}: {took:.3f} ms a "
            f"launch, a grid step {pages} page(s) of {heads} heads (host "
            f"clock, the best of three times {k['iters']} calls of {L} "
            f"layers)")
    return failures


def _window_kernel(cfg, rand, rng) -> list:
    """``tnn_paged_attention_win`` against its XLA form: a lower bound a
    query, a walk that starts at the first query's bound, tables that list a
    row's pages from ``table_base`` on (one of them a page behind: given
    back only at the next commit), decode and chunk shapes."""
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas.paged_attention import (paged_attention,
                                                    window_table_pages)

    k = cfg["window"]
    bs, B, W = k["block_size"], k["batch"], k["window"]
    nb = window_table_pages(W, bs)
    pk, pv = (rand((1, k["blocks"], k["kv_heads"], bs, k["head_dim"]))
              for _ in range(2))
    tables = jnp.asarray(rng.integers(1, k["blocks"], (B, nb)), jnp.int32)
    failures = []
    for fname, qw in (("decode", 1), (f"chunk{k['chunk']}", k["chunk"])):
        kv_lens = np.array([qw, W // 2, W, W + 1, W + bs - 1, 3 * W + 5,
                            7 * W + bs // 2, 9 * W])[:B].astype(np.int32)
        q_lens = np.minimum(np.array([qw, 1, qw // 2 + 1] * B)[:B], kv_lens)
        base = np.maximum(kv_lens - q_lens - W + 1, 0) // bs
        base[::3] = np.maximum(base[::3] - 1, 0)
        q = rand((B, qw, k["heads"], k["head_dim"]))
        kw = dict(q_lens=jnp.asarray(q_lens, jnp.int32), window=W,
                  table_base=jnp.asarray(base, jnp.int32))
        args = (q, pk, pv, tables, jnp.asarray(kv_lens))
        got = np.asarray(paged_attention(
            *args, backend="pallas", interpret=cfg["rehearse"],
            group_positions=4 * bs, **kw), np.float32)
        want = np.asarray(paged_attention(*args, backend="xla", **kw),
                          np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.isfinite(got).all()) and err <= KERNEL_TOL["bf16"]
        log(f"{'window ' + fname:28s} max|err| {err:9.2e} tol "
            f"{KERNEL_TOL['bf16']:6.0e} {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"kernel window {fname}: error above "
                            f"{KERNEL_TOL['bf16']}")
    return failures


def _check_close(failures, name, got, want, tol) -> None:
    """One line of the kernel table: the largest error of ``got`` against
    ``want``, held to ``tol``; a failure goes into ``failures``."""
    import numpy as np

    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(g - w)))
    ok = bool(np.isfinite(g).all()) and err <= tol
    log(f"{name:28s} max|err| {err:9.2e} tol {tol:6.0e} "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(f"kernel {name}: error above {tol}")


def _latent_and_expert_kernels(cfg, rand, rng) -> list:
    """``tnn_mla_attention`` and ``tnn_expert_gmm`` against their XLA forms
    (the gather and one dense product an expert), decode and chunk shapes."""
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.ops.pallas.expert_gmm import expert_gmm, row_tile
    from tnn_tpu.ops.pallas.mla_attention import mla_attention

    interpret, failures = cfg["rehearse"], []

    check = functools.partial(_check_close, failures)

    for k in (cfg["latent"], cfg["latent_wide"]):
        B, nb, bs, h = k["batch"], k["table"], k["block_size"], k["heads"]
        pages = rand((2, k["blocks"], 1, bs, k["row"]))
        tables = jnp.asarray(rng.integers(1, k["blocks"], (B, nb)),
                             jnp.int32)
        cap = nb * bs
        kv_lens = jnp.asarray(np.array(
            [1, bs, bs + 1, cap // 3, cap // 2 - 1, cap - bs - 5, cap - 24,
             cap])[:B].clip(1, cap), jnp.int32)
        for qw in filter(None, (1, k["chunk"])):
            q = rand((B, qw, h, k["row"]))
            q_lens = jnp.minimum(jnp.asarray(
                np.array([qw, 1, qw // 2 + 1] * B)[:B], jnp.int32), kv_lens)
            kw = dict(value_dim=k["value"], q_lens=q_lens, layer=1,
                      scale=k["row"] ** -0.5)
            check(f"mla {'decode' if qw == 1 else f'chunk{qw}'} "
                  f"{h} heads x {k['row']}",
                  mla_attention(q, pages, tables, kv_lens, backend="pallas",
                                interpret=interpret, **kw),
                  mla_attention(q, pages, tables, kv_lens, backend="xla",
                                **kw), KERNEL_TOL["bf16"])
    for e in (cfg["experts"], cfg["experts_wide"]):
        n, f, d = e["held"], e["hidden"], e["width"]
        gate, up, down = (rand((n, f, d)) * (d ** -0.5) for _ in range(3))
        for tokens in e["tokens"]:
            tile = row_tile(4 * tokens)
            # every expert but the last gets rows (uneven), two tiles are
            # dead
            sizes = rng.multinomial(tokens, np.ones(n - 1) / (n - 1))
            tile_expert = np.repeat(np.arange(n - 1), -(-sizes // tile))
            live = len(tile_expert)
            tile_expert = np.concatenate([tile_expert, [tile_expert[-1]] * 2])
            x = np.zeros((len(tile_expert) * tile, d), np.float32)
            at = 0
            for cnt in sizes:
                x[at:at + cnt] = rng.standard_normal((cnt, d))
                at += -(-cnt // tile) * tile
            args = (jnp.asarray(x, jnp.bfloat16), gate, up, down,
                    jnp.asarray(tile_expert, jnp.int32), jnp.int32(live))
            got = expert_gmm(*args, tile=tile, backend="pallas",
                             interpret=interpret)
            want = expert_gmm(*args, tile=tile, backend="xla")
            check(f"expert_gmm {tokens} rows x {d}, tile {tile}",
                  got[:live * tile], want[:live * tile],
                  4 * KERNEL_TOL["bf16"])
    return failures


def _sampler_steps(cfg, rng) -> list:
    """``sampling.sample_ragged`` under jit at the served vocabularies: a
    step of greedy rows (the argmax alone) and a step with ONE sampled row
    (one sort of the vocabulary, the softmax, the draw), timed back to back
    from the host around ``block_until_ready``; both hold every greedy row to
    the argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.models.sampling import sample_ragged

    failures = []
    step = jax.jit(sample_ragged)
    for rows, v in cfg["sampler"]["shapes"]:
        logits = jnp.asarray(rng.standard_normal((rows, v)), jnp.float32)
        best = np.asarray(jnp.argmax(logits, axis=-1))
        k = jnp.full((rows,), 40, jnp.int32)
        p = jnp.full((rows,), 0.9, jnp.float32)
        greedy = jnp.zeros((rows,), jnp.float32)
        kinds = {"greedy": greedy, "one_sampled": greedy.at[0].set(0.8)}
        keys = list(jax.random.split(jax.random.PRNGKey(1),
                                     cfg["sampler"]["iters"]))
        took = {}
        for kind, t in kinds.items():
            got = np.asarray(step(logits, keys[0], t, k, p))     # compiles
            first = 0 if kind == "greedy" else 1    # row 0 is the drawn one
            if not ((got[first:] == best[first:]).all()
                    and 0 <= got[0] < v):
                failures.append(f"sampler {kind} ({rows}, {v}): a greedy "
                                f"row is not the argmax")
            t0 = time.perf_counter()
            for key in keys:
                out = step(logits, key, t, k, p)
            jax.block_until_ready(out)
            took[kind] = (time.perf_counter() - t0) / len(keys) * 1e3
        log(f"sample_ragged ({rows}, {v}): greedy step "
            f"{took['greedy']:.3f} ms, step with one sampled row "
            f"{took['one_sampled']:.3f} ms (host clock, "
            f"{cfg['sampler']['iters']} calls each)")
    return failures


# ----------------------------------------------------------------- serve ----

class _Tee(io.TextIOBase):
    """Forward writes to ``stream`` and keep a copy."""

    def __init__(self, stream):
        self.stream = stream
        self.buf = io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


class _EventSink(io.TextIOBase):
    """Stands in for the server's stdout: parses the JSON event lines and
    lets the client thread wait for a request to finish."""

    def __init__(self, passthrough):
        self.events = []
        self.cv = threading.Condition()
        self.server_gone = False
        self._part = ""
        self._passthrough = passthrough

    def write(self, s):
        with self.cv:
            self._part += s
            *lines, self._part = self._part.split("\n")
            for line in lines:
                try:
                    self.events.append(json.loads(line))
                except ValueError:     # not an event: somebody's print()
                    self._passthrough.write(line + "\n")
            self.cv.notify_all()
        return len(s)

    def close_sink(self):
        with self.cv:
            self.server_gone = True
            self.cv.notify_all()

    def wait_terminal(self, user_ids, timeout):
        """Block until every request in ``user_ids`` has ended (or the
        server is gone)."""
        want = set(user_ids)

        def seen():
            ended = {e.get("id") for e in self.events
                     if e.get("event") != "token"}
            return self.server_gone or want <= ended
        with self.cv:
            return self.cv.wait_for(seen, timeout)


def make_requests(cfg, vocab_size: int):
    """Token-id requests: sub-chunk, multi-chunk and many-page prompts, the
    last of the first wave publishing a prefix that the late requests share.
    Returns (waves, by_id)."""
    import numpy as np

    rng = np.random.default_rng(0)

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab_size, n)]

    prefix = toks(cfg["prefix_len"])
    prompts = [toks(n) for n in cfg["prompt_lens"]]
    # the publisher and the sharers: same prefix, different tails
    prompts += [prefix + toks(n - len(prefix)) for n in cfg["sharer_lens"]]
    reqs = [{"id": i, "tokens": p, "max_new_tokens": cfg["max_new"]}
            for i, p in enumerate(prompts)]
    return [reqs[:-1], reqs[-1:]], {r["id"]: r for r in reqs}


def drive_serve(argv, waves, timeout_s=900.0):
    """Run ``tnn_tpu.cli.serve.main(argv)`` in this process the way a user
    runs ``tnn-serve``: requests go in as JSON lines on stdin (a pipe fed by
    a client thread), events come back on stdout, the summary on stderr.
    The client sends one wave of requests at a time and waits for all of it
    to end before the next (so the first wave's prefix is published before
    the sharer arrives), then closes stdin, which drains an idle server —
    the 30 s drain deadline must never race a cold compile.
    Returns (rc, events, stderr_text, engines)."""
    import tnn_tpu.cli.serve as serve_cli

    engines = []

    class Capture(serve_cli.InferenceEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    rfd, wfd = os.pipe()
    sink = _EventSink(passthrough=sys.stderr)
    err = _Tee(sys.stderr)

    def client():
        try:
            with os.fdopen(wfd, "w") as w:
                for wave in waves:
                    for r in wave:
                        w.write(json.dumps(r) + "\n")
                    w.flush()
                    sink.wait_terminal([r["id"] for r in wave], timeout_s)
        except OSError:
            pass    # the server went away first; its exit code says why

    t = threading.Thread(target=client, name="smoke-client", daemon=True)
    with os.fdopen(rfd, "r") as rd, \
            mock.patch.object(serve_cli, "InferenceEngine", Capture), \
            mock.patch.object(sys, "stdin", rd), \
            contextlib.redirect_stdout(sink), \
            contextlib.redirect_stderr(err):
        t.start()
        try:
            rc = serve_cli.main(argv)
        finally:
            sink.close_sink()
            t.join(10.0)
    return rc, sink.events, err.buf.getvalue(), engines


def parse_summary(stderr_text: str) -> dict:
    for line in reversed(stderr_text.splitlines()):
        if line.startswith("serve summary: "):
            return json.loads(line[len("serve summary: "):])
    return {}


def check_serve(by_id, events, summary, vocab_size, *,
                zero_keys=("failed", "engine_restarts", "step_retries"),
                want_prefix_hit=True) -> list:
    """The smoke's verdict on one serve run, from what a client and an
    operator can see: the event stream and the summary. A failure the engine
    caught and isolated is still a failure here."""
    failures = []
    terminal = {}
    for e in events:
        if e.get("event") in ("token", None):
            continue
        terminal.setdefault(e.get("id"), []).append(e)
    for rid, req in by_id.items():
        evs = terminal.get(rid, [])
        if len(evs) != 1 or evs[0].get("event") != "done":
            failures.append(
                f"request {rid} (prompt {len(req['tokens'])}): "
                + (f"ended {evs[0].get('event')}: {evs[0].get('reason')}"
                   if evs else "no terminal event"))
            continue
        ev = evs[0]
        new = ev.get("tokens", [])
        if ev.get("finish_reason") != "length":
            failures.append(f"request {rid}: finish_reason "
                            f"{ev.get('finish_reason')!r}, want 'length'")
        if len(new) != req["max_new_tokens"]:
            failures.append(f"request {rid}: {len(new)} new tokens, want "
                            f"{req['max_new_tokens']}")
        if any(not (0 <= int(t) < vocab_size) for t in new):
            failures.append(f"request {rid}: token outside the vocabulary")
    stray = [e for e in events if e.get("event") == "error"
             and e.get("id") not in by_id]
    failures += [f"server error event: {e.get('reason')}" for e in stray]
    if not summary:
        failures.append("no 'serve summary' line on stderr")
    for k in zero_keys:
        if summary.get(k, "missing") != 0:
            failures.append(f"summary {k} = {summary.get(k, 'missing')}, "
                            "want 0")
    if want_prefix_hit and not summary.get("prefill_tokens_saved", 0) > 0:
        failures.append("summary prefill_tokens_saved = "
                        f"{summary.get('prefill_tokens_saved')}, want > 0 "
                        "(the shared prefix never hit the cache)")
    return failures


def _pages_array(pages):
    return pages.data if hasattr(pages, "data") else pages


def check_engine(engine, *, want_platform, want_devices=1, interpret) -> list:
    """What only the process that holds the engine can see."""
    from tnn_tpu.ops.pallas.runtime import interpret_default

    failures = []
    if interpret_default() != interpret:
        failures.append(f"interpret_default() is {interpret_default()}, "
                        f"want {interpret}")
    devs = _pages_array(engine.pool.pages_k).sharding.device_set
    if {d.platform for d in devs} != {want_platform}:
        failures.append(f"pool pages on {sorted(str(d) for d in devs)}, "
                        f"want platform {want_platform!r}")
    if len(devs) != want_devices:
        failures.append(f"pool pages on {len(devs)} device(s), want "
                        f"{want_devices}")
    return failures


class Reference:
    """Plain full-sequence ``model.apply`` forward on one device, the
    yardstick every served token is held against."""

    def __init__(self, model_name: str, seed: int = 0):
        import jax

        from tnn_tpu import models

        self.model = models.create(model_name)
        # exactly what tnn-serve builds when given no --model-file
        self.params = self.model.init(jax.random.PRNGKey(seed),
                                      (1, 8))["params"]
        model = self.model

        @jax.jit
        def rows(params, ids, pos):
            logits, _ = model.apply({"params": params, "state": {}}, ids)
            return logits[0][pos]                      # (len(pos), V) f32

        self._rows = rows

    def logits_at(self, ids, positions):
        """Logits predicting token ``p + 1`` for each p in ``positions``.
        The sequence is padded to the model's max_len (causal: padding never
        reaches an earlier position), so one program serves every length."""
        import numpy as np

        buf = np.zeros((1, self.model.max_len), np.int32)
        buf[0, :len(ids)] = ids
        return np.asarray(self._rows(self.params, buf,
                                     np.asarray(positions, np.int32)))

    def token_gaps(self, prompt, new_tokens):
        """For each generated token: how far its logit sits below the
        arg-max of the reference distribution at its position (0 = the
        reference's own greedy choice)."""
        import numpy as np

        ids = list(prompt) + list(new_tokens)
        pos = np.arange(len(prompt) - 1, len(ids) - 1)
        lg = self.logits_at(ids, pos)
        return lg.max(axis=-1) - lg[np.arange(len(pos)), np.asarray(new_tokens)]


def check_tokens(ref: Reference, by_id, events) -> list:
    import numpy as np

    failures = []
    worst = 0.0
    exact = total = 0
    for e in events:
        if e.get("event") != "done" or e.get("id") not in by_id:
            continue
        gaps = ref.token_gaps(by_id[e["id"]]["tokens"], e["tokens"])
        if not np.isfinite(gaps).all() or gaps.max() > LOGIT_TOL:
            failures.append(
                f"request {e['id']}: generated token {int(np.argmax(gaps))} "
                f"sits {gaps.max():.3f} below the reference arg-max "
                f"(tolerance {LOGIT_TOL})")
        worst = max(worst, float(gaps.max()))
        exact += int((gaps == 0).sum())
        total += len(gaps)
    log(f"tokens vs plain forward: {exact}/{total} are the reference's own "
        f"arg-max, worst logit gap {worst:.4f} (tolerance {LOGIT_TOL})")
    return failures


def check_paged_logits(cfg, ref: Reference, prompts) -> list:
    """The model's paged path — prompt chunks through ``apply_paged`` into a
    pool, as the engine's mixed step runs them — against the plain forward,
    first-token logits compared directly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnn_tpu.serving import PagedKVPool

    model, bs, chunk = ref.model, cfg["block_size"], 64
    nb = -(-model.max_len // bs)
    pool = PagedKVPool(
        num_layers=model.num_layers, num_kv_heads=model.num_kv_heads,
        head_dim=model.d_model // model.num_heads, num_blocks=nb + 1,
        block_size=bs, dtype=model.policy.compute_dtype)
    step = jax.jit(model.apply_paged, donate_argnums=(2, 3))
    failures = []
    for prompt in prompts:
        table = pool.alloc(pool.blocks_for(len(prompt)))
        tables = jnp.asarray(pool.padded_table(table, nb))[None]
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(piece)] = piece
            logits, pk, pv = step(
                ref.params, jnp.asarray(toks), pool.pages_k, pool.pages_v,
                tables, jnp.asarray([start], jnp.int32),
                jnp.asarray([len(piece)], jnp.int32))
            pool.update_pages(pk, pv)
        got = np.asarray(logits[0, len(piece) - 1], np.float32)
        want = ref.logits_at(prompt, [len(prompt) - 1])[0]
        err = float(np.max(np.abs(got - want)))
        ok = np.isfinite(got).all() and err <= LOGIT_TOL
        log(f"paged first-token logits, prompt {len(prompt):4d}: max abs "
            f"diff {err:.4f} vs plain forward (spread {want.std():.3f}, "
            f"tolerance {LOGIT_TOL}) {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"paged logits differ by {err:.3f} on a "
                            f"{len(prompt)}-token prompt")
        pool.free(table)
    return failures


def run_serve(cfg, ref: Reference, *extra, want_devices=1,
              zero_keys=("failed", "engine_restarts", "step_retries"),
              want_prefix_hit=True):
    """One full serve run + every check on it. Returns (failures, engines)."""
    vocab = ref.model.vocab_size
    waves, by_id = make_requests(cfg, vocab)
    argv = ["--model", cfg["model"], "--num-blocks", str(cfg["num_blocks"]),
            "--block-size", str(cfg["block_size"]),
            "--max-batch-size", str(cfg["max_batch"]), *extra]
    log(f"$ tnn-serve {' '.join(argv)}   # {len(by_id)} requests on stdin")
    t0 = time.perf_counter()
    rc, events, stderr_text, engines = drive_serve(argv, waves)
    wall = time.perf_counter() - t0
    summary = parse_summary(stderr_text)
    failures = []
    if rc != 0:
        failures.append(f"tnn-serve exit code {rc}")
    failures += check_serve(by_id, events, summary, vocab,
                            zero_keys=zero_keys,
                            want_prefix_hit=want_prefix_hit)
    for eng in engines:
        failures += check_engine(
            eng, want_platform="cpu" if cfg["rehearse"] else "tpu",
            want_devices=want_devices, interpret=cfg["rehearse"])
    if not engines:
        failures.append("no engine was built")
    failures += check_tokens(ref, by_id, events)
    done = sum(e.get("event") == "done" for e in events)
    keys = sorted(str(k) for eng in engines for k in eng._jit)
    notes = [f"{k}={summary[k]}" for k in (
        "prefill_tokens_saved", "hedges_fired", "migrated_requests",
        "degraded_ejections") if summary.get(k)]
    log(f"{done}/{len(by_id)} done in {wall:.1f} s wall (compilation "
        f"included); " + "; ".join(
            notes + [f"compiled step programs: {', '.join(keys)}"]))
    return failures, engines


def phase_serve(cfg) -> list:
    ref = cfg["ref"] = Reference(cfg["model"])
    failures, engines = run_serve(cfg, ref)
    del engines
    gc.collect()
    _, by_id = make_requests(cfg, ref.model.vocab_size)
    longest = max(by_id.values(), key=lambda r: len(r["tokens"]))["tokens"]
    failures += check_paged_logits(cfg, ref, [by_id[1]["tokens"], longest])
    return failures


# ----------------------------------------------------------------- train ----

def run_trainer(cfg, *extra, max_steps=-1):
    """``tnn_tpu.cli.trainer.main`` as a user runs it. A --config file turns
    the progress print on for every step (that is where the trainer reports
    its loss) and keeps snapshots and the log out of the checkout."""
    from tnn_tpu.cli import trainer

    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as tmp:
        conf = os.path.join(tmp, "config.json")
        log_file = os.path.join(tmp, "train.log")
        with open(conf, "w") as f:
            json.dump({"progress_print_interval": 1, "max_steps": max_steps,
                       "snapshot_dir": os.path.join(tmp, "snapshots"),
                       "log_file": log_file}, f)
        argv = ["--model", cfg["train_model"], "--dataset", "synthetic",
                "--num-classes", str(cfg["train_classes"]),
                "--batch-size", str(cfg["train_batch"]), "--epochs", "1",
                "--config", conf, *extra]
        log(f"$ tnn-trainer {' '.join(argv)}")
        state, history = trainer.main(argv)
        with open(log_file) as f:
            text = f.read()
    steps = [(int(b), float(loss), float(rate)) for b, loss, rate in re.findall(
        r"batch (\d+): loss=(\S+) acc=\S+ \S+ ms/batch \((\S+) samples/s\)",
        text)]
    return state, history, steps


def check_train(cfg, state, history, steps, want_steps) -> list:
    import jax
    import numpy as np

    from tnn_tpu import models

    failures = []
    losses = [loss for _, loss, _ in steps]
    if [b for b, _, _ in steps] != list(range(1, want_steps + 1)):
        failures.append(f"trainer logged {len(steps)} steps, want "
                        f"{want_steps}")
    if not losses or not np.isfinite(losses).all():
        failures.append(f"non-finite loss in {losses}")
    if int(state.step) != want_steps:
        failures.append(f"state.step = {int(state.step)}, want {want_steps}")
    h = history[-1] if history else {}
    if not np.isfinite(h.get("val_loss", float("nan"))):
        failures.append(f"validation loss {h.get('val_loss')}")
    # the trainer's own init, from its own seed: did training move it?
    shape = (28, 28, 1) if "mnist" in cfg["train_model"] else (32, 32, 3)
    init = models.create(cfg["train_model"]).init(
        jax.random.split(jax.random.PRNGKey(0))[0],
        (cfg["train_batch"],) + shape)["params"]
    moved = sum(float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).sum())
                for a, b in zip(jax.tree_util.tree_leaves(state.params),
                                jax.tree_util.tree_leaves(init)))
    if not (np.isfinite(moved) and moved > 0):
        failures.append(f"parameters did not move (sum |delta| = {moved})")
    rates = sorted(r for _, _, r in steps[5:])
    log(f"{len(steps)} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}, val "
        f"loss {h.get('val_loss', float('nan')):.4f}, sum|param delta| "
        f"{moved:.3e}"
        + (f"; median {rates[len(rates) // 2]:.0f} img/s with a loss fetch "
           "every step (informational, not a benchmark)" if rates else ""))
    return failures


def phase_train(cfg) -> list:
    state, history, steps = run_trainer(cfg)
    return check_train(cfg, state, history, steps, want_steps=50)


# ------------------------------------------------------------- four chip ----

def phase_four_chip(cfg) -> list:
    import jax

    from tnn_tpu.utils.hardware import hbm_stats

    n = cfg["degree"]
    devs = jax.devices()
    if len(devs) < n:
        log(f"skipped: needs {n} devices, found {len(devs)}")
        return []
    ref = cfg.get("ref") or Reference(cfg["model"])
    failures = []

    def state_on_all(label, device_set, min_bytes):
        out = []
        if len(device_set) != n:
            out.append(f"{label}: state on {len(device_set)} device(s), "
                       f"want {n}")
        used = [hbm_stats(d).get("bytes_in_use", -1) for d in devs[:n]]
        log(f"{label}: bytes_in_use per device {used}")
        if not cfg["rehearse"] and min(used) < min_bytes:
            out.append(f"{label}: a device holds {min(used)} bytes, want "
                       f">= {min_bytes}")
        return out

    for flag in ("--tp", "--sp"):
        f, engines = run_serve(cfg, ref, flag, str(n), want_devices=n)
        pool_devs = {d for e in engines
                     for d in _pages_array(e.pool.pages_k).sharding.device_set}
        # every shard holds a quarter of the pool at the least
        f += state_on_all(f"serve {flag} {n}", pool_devs, 16 << 20)
        failures += [f"{flag} {n}: {x}" for x in f]
        del engines
        gc.collect()

    state, history, steps = run_trainer(
        cfg, "--mesh", f"data={n}", max_steps=cfg["mesh_steps"])
    f = check_train(cfg, state, history, steps, want_steps=cfg["mesh_steps"])
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    f += state_on_all(f"train --mesh data={n}", leaf.sharding.device_set,
                      16 << 20)
    failures += [f"--mesh data={n}: {x}" for x in f]
    del state
    gc.collect()
    return failures + check_replicas(cfg, ref, n)


def check_replicas(cfg, ref: Reference, n: int) -> list:
    """``--replicas n``: the fleet serves, and replica i lives on device i."""
    import jax

    devs = jax.devices()
    # a cold fleet may hedge or migrate a stream while one replica is still
    # compiling (router policy, token-exact by contract) — not a failure;
    # the sharer may land on another replica than the publisher
    f, engines = run_serve(cfg, ref, "--replicas", str(n),
                           zero_keys=("failed", "replica_restarts"),
                           want_prefix_hit=False)
    for i, eng in enumerate(engines):
        pool_dev = sorted(_pages_array(eng.pool.pages_k).sharding.device_set,
                          key=lambda d: d.id)
        param_dev = sorted(jax.tree_util.tree_leaves(eng.params)[0]
                           .sharding.device_set, key=lambda d: d.id)
        log(f"replica {i}: params on {[str(d) for d in param_dev]}, pool on "
            f"{[str(d) for d in pool_dev]}")
        if pool_dev != [devs[i]] or param_dev != [devs[i]]:
            f.append(f"replica {i} is not on device {i}")
    if len(engines) != n:
        f.append(f"{len(engines)} engines built, want {n}")
    return [f"--replicas {n}: {x}" for x in f]


# ------------------------------------------------------------------ main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="NOT a chip run: toy model, interpreted kernels, "
                         "whatever backend JAX has — for debugging this "
                         "script without a chip. Never prints the ok result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES)
                         + " (the device phase always runs)")
    args = ap.parse_args(argv)
    want = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(want) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s): {', '.join(unknown)}")

    try:
        import tnn_tpu  # noqa: F401 — the program this script drives
    except ImportError as e:
        print(f"chip_smoke.py: {e} — run it from the root of a checkout.",
              file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse:
        log("REHEARSAL — not a chip run: toy model, interpreted kernels, "
            f"platform {device['platform']!r}. Proves only that this script "
            "runs.")
    elif device["platform"] != "tpu":
        print(f"chip_smoke.py: no TPU — JAX found {device['count']} x "
              f"{device['platform']!r}. This script does not run on a CPU "
              "(--rehearse is the labelled sandbox rehearsal).",
              file=sys.stderr)
        return 2

    cfg = dict(REHEARSAL if args.rehearse else CHIP, rehearse=args.rehearse)
    phases = {"device": phase_device, "kernel": phase_kernel,
              "serve": phase_serve, "train": phase_train,
              "four_chip": phase_four_chip}
    failed = {}
    t_all = time.perf_counter()
    for name in PHASES:
        if name != "device" and name not in want:
            continue
        log(f"== {name} ==")
        t0 = time.perf_counter()
        try:
            failures = phases[name](cfg)
        except Exception:  # noqa: BLE001 — report the phase, run the rest
            traceback.print_exc()
            failures = [f"raised {traceback.format_exc().splitlines()[-1]}"]
        for f in failures:
            log(f"  FAIL: {f}")
        log(f"{name}: {'FAILED' if failures else 'passed'} "
            f"({time.perf_counter() - t0:.1f} s)")
        if failures:
            failed[name] = failures
    from tnn_tpu.utils import compile_cache

    log(f"total {time.perf_counter() - t_all:.1f} s; compile cache now "
        f"{compile_cache.describe(cfg.get('cache_dir'))}")
    sys.stderr.flush()
    ran = [p for p in PHASES if p == "device" or p in want]
    if failed:
        log(json.dumps({"ok": False, "failed_phases": sorted(failed),
                        "phases_run": ran, "device": device}))
        return 1
    if args.rehearse or ran != list(PHASES):
        # a rehearsal or a partial run proves part of the contract at most:
        # it passes without the ok result
        log(json.dumps({"rehearsal" if args.rehearse else "partial": "passed",
                        "phases_run": ran, "device": device}))
        return 0
    log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
