"""Fused whole-stack decode kernel: every transformer block of one decode step
in ONE Pallas call.

Why: bs=1 autoregressive decode is latency-bound on op DISPATCH, not math.
The unfused int8 decode step issues ~1000 XLA ops per token (49 matmuls +
norms/attention/cache plumbing x 12 layers); profiling on the v5e chip showed
~75ns of sequencer gap per op plus sub-us fusions adding up to ~55% of the
254us/token device time. This kernel collapses the entire L-layer stack into a
single launch: the residual stream lives in a VMEM scratch accumulator across
a (layers, mlp-chunks) grid, per-layer int8 weights stream in as
double-buffered VMEM blocks, and the KV cache stays in HBM — each step DMAs
layer l's cache into VMEM, appends the new row at position t, and writes just
that row back through an aliased output.

Numerics exactly mirror the unfused w8a8 decode path (quant_matmul.w8a8_matmul):
activations are re-quantized to int8 per row at each matmul input (ln1 out,
attention context, ln2 out, gelu out), contractions run int8 x int8 -> int32 on
the MXU, and the per-row / per-output-channel scales multiply the int32
accumulator. The one intentional difference: the MLP runs in C chunks of the
hidden dim F (to fit VMEM), so the gelu-output quantization scale is per-chunk
absmax rather than whole-row — a strictly finer-grained (more accurate)
quantization.

Attention without per-head batched matmuls (B is tiny, T is the long axis):
  scores(h,t') = sum_d maskq[h,d] * k[t',d]   with maskq = one_hot(head) * q
one "nt" MXU gemm (Hp=128 padded heads x T), masked online over positions <= t,
then ctx(h,d) = probs @ V (one "nn" gemm) and a head-select reduction back to
(1, D). Requires head_dim == 64 x const? No — only that D = H * Dh; the head
select masks are built from iota at trace time.

Reference anchor: the reference's inference loop re-runs the FULL sequence
through the graph per generated token (examples/gpt2_inference.cpp:71-122);
this kernel is the TPU-native opposite end of that design space.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HP = 128  # heads padded to one lane tile; H <= 128 covers every GPT-2 size


def _layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    mean2 = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return y * scale + bias


def _quant_rows(x):
    """Per-row symmetric int8 quantization (matches w8a8_matmul)."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    xi = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    return xi, sx


def _i8dot_nt(xi, w_q):
    """(B, K) i8 x (N, K) i8 -> (B, N) i32 on the MXU."""
    return jax.lax.dot_general(xi, w_q, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _decode_kernel(t_ref, x_ref, kc, vc,
                   ln1_s, ln1_b, ln2_s, ln2_b,
                   qkv_q, qkv_s, qkv_b, out_q, out_s, out_b,
                   fc_q, fc_s, fc_b, proj_q, proj_s, proj_b,
                   x_out, kc_out, vc_out,
                   x_acc, h_ln2, kbuf, vbuf, sem_k, sem_v, sem_wb,
                   *, num_heads: int, chunks: int, scale: float):
    l = pl.program_id(0)
    c = pl.program_id(1)
    t = t_ref[0]
    B, D = x_acc.shape
    T = kbuf.shape[1]
    dh = D // num_heads

    @pl.when(jnp.logical_and(l == 0, c == 0))
    def _init():
        x_acc[...] = x_ref[...].astype(jnp.float32)

    @pl.when(c == 0)
    def _attention():
        ck = pltpu.make_async_copy(kc.at[l], kbuf, sem_k)
        cv = pltpu.make_async_copy(vc.at[l], vbuf, sem_v)
        ck.start()
        cv.start()

        x = x_acc[...]
        h = _layernorm(x, ln1_s[...], ln1_b[...])
        hi, hs = _quant_rows(h)
        qkv = (_i8dot_nt(hi, qkv_q[0]).astype(jnp.float32)
               * hs * qkv_s[...] + qkv_b[...])          # (B, 3D) f32
        q = qkv[:, :D]
        k_new = qkv[:, D:2 * D]
        v_new = qkv[:, 2 * D:]

        ck.wait()
        cv.wait()
        kbuf[:, pl.ds(t, 1), :] = k_new[:, None, :].astype(kbuf.dtype)
        vbuf[:, pl.ds(t, 1), :] = v_new[:, None, :].astype(vbuf.dtype)

        # head-select masks from iota: mask_hd[h, d] = (d // dh == h)
        hid = jax.lax.broadcasted_iota(jnp.int32, (_HP, D), 0)
        did = jax.lax.broadcasted_iota(jnp.int32, (_HP, D), 1)
        mask_hd = (did // dh == hid).astype(jnp.float32)    # (Hp, D)
        live = (jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) <= t)

        # per-batch-row attention, accumulated into h_ln2's buffer reused as
        # ctx scratch via static row slices (Mosaic's concatenate support is
        # limited; indexed stores are not). B is tiny (decode); unrolled.
        for b in range(B):
            qmask = mask_hd * q[b:b + 1, :]                  # (Hp, D)
            kb = kbuf[b].astype(jnp.float32)                 # (T, D)
            scores = jax.lax.dot_general(
                qmask, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (Hp, T)
            scores = jnp.where(live, scores, -jnp.inf)
            m = jnp.max(scores, axis=-1, keepdims=True)
            p = jnp.exp(scores - m)
            p = p / jnp.sum(p, axis=-1, keepdims=True)       # (Hp, T)
            vb = vbuf[b].astype(jnp.float32)                 # (T, D)
            ctx_full = jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (Hp, D)
            h_ln2[b:b + 1, :] = jnp.sum(ctx_full * mask_hd, axis=0,
                                        keepdims=True)       # (1, D)
        ctx = h_ln2[...]

        ci, cs = _quant_rows(ctx)
        attn_out = (_i8dot_nt(ci, out_q[0]).astype(jnp.float32)
                    * cs * out_s[...] + out_b[...])
        x_mid = x + attn_out
        h_ln2[...] = _layernorm(x_mid, ln2_s[...], ln2_b[...])
        # proj bias added once (chunk partials accumulate on top)
        x_acc[...] = x_mid + proj_b[...]

        # write the appended row back to the HBM cache (aliased in/out)
        wk = pltpu.make_async_copy(kbuf.at[:, pl.ds(t, 1), :],
                                   kc_out.at[l, :, pl.ds(t, 1), :], sem_wb)
        wk.start()
        wk.wait()
        wv = pltpu.make_async_copy(vbuf.at[:, pl.ds(t, 1), :],
                                   vc_out.at[l, :, pl.ds(t, 1), :], sem_wb)
        wv.start()
        wv.wait()

    # MLP chunk c: x_acc += proj_c(gelu(fc_c(h_ln2)))
    hi, hs = _quant_rows(h_ln2[...])
    fc = (_i8dot_nt(hi, fc_q[0]).astype(jnp.float32)
          * hs * fc_s[...] + fc_b[...])                      # (B, F/C)
    g = jax.nn.gelu(fc, approximate=True)
    gi, gs = _quant_rows(g)
    part = (_i8dot_nt(gi, proj_q[0]).astype(jnp.float32)
            * gs * proj_s[...])                              # (B, D)
    x_acc[...] = x_acc[...] + part
    x_out[...] = x_acc[...].astype(x_out.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_heads", "chunks", "interpret"))
def fused_decode_stack(x, t, k_cache, v_cache, stacks: Dict[str, Any], *,
                       num_heads: int, chunks: int = 2,
                       interpret: bool = False):
    """Run all L transformer blocks of one decode step in one Pallas call.

    x: (B, D) embedded token (wte + wpe). t: scalar int32 position (number of
    cached positions). k_cache/v_cache: (L, B, T, D) in compute dtype —
    DONATED/aliased, updated in place at position t. stacks: layer-stacked
    weights from models.fused_decode.stack_decode_weights.
    Returns (x_out (B, D), k_cache, v_cache).
    """
    B, D = x.shape
    L, Bc, T, Dc = k_cache.shape
    assert (Bc, Dc) == (B, D), (k_cache.shape, x.shape)
    F = stacks["fc_s"].shape[1]  # full hidden dim
    assert F % chunks == 0, (F, chunks)
    fchunk = F // chunks
    scale = 1.0 / (D // num_heads) ** 0.5

    t_arr = jnp.reshape(t, (1,)).astype(jnp.int32)

    def vec(name, last):
        # per-layer vectors as (L, last) f32, block (1, last)
        return pl.BlockSpec((1, last), lambda l, c: (l, 0),
                            memory_space=pltpu.VMEM)

    grid = (L, chunks)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                       # t
        pl.BlockSpec((B, D), lambda l, c: (0, 0),
                     memory_space=pltpu.VMEM),                       # x
        pl.BlockSpec(memory_space=pl.ANY),                           # k_cache
        pl.BlockSpec(memory_space=pl.ANY),                           # v_cache
        vec("ln1_s", D), vec("ln1_b", D), vec("ln2_s", D), vec("ln2_b", D),
        pl.BlockSpec((1, 3 * D, D), lambda l, c: (l, 0, 0),
                     memory_space=pltpu.VMEM),                       # qkv_q
        vec("qkv_s", 3 * D), vec("qkv_b", 3 * D),
        pl.BlockSpec((1, D, D), lambda l, c: (l, 0, 0),
                     memory_space=pltpu.VMEM),                       # out_q
        vec("out_s", D), vec("out_b", D),
        pl.BlockSpec((1, fchunk, D), lambda l, c: (l, c, 0),
                     memory_space=pltpu.VMEM),                       # fc_q
        pl.BlockSpec((1, fchunk), lambda l, c: (l, c),
                     memory_space=pltpu.VMEM),                       # fc_s
        pl.BlockSpec((1, fchunk), lambda l, c: (l, c),
                     memory_space=pltpu.VMEM),                       # fc_b
        pl.BlockSpec((1, D, fchunk), lambda l, c: (l, 0, c),
                     memory_space=pltpu.VMEM),                       # proj_q
        vec("proj_s", D), vec("proj_b", D),
    ]
    out_specs = [
        pl.BlockSpec((B, D), lambda l, c: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, D), x.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    kern = functools.partial(_decode_kernel, num_heads=num_heads,
                             chunks=chunks, scale=scale)
    f = pl.pallas_call(
        kern, name="tnn_decode_stack", grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        input_output_aliases={2: 1, 3: 2},
        scratch_shapes=[
            pltpu.VMEM((B, D), jnp.float32),        # x_acc
            pltpu.VMEM((B, D), jnp.float32),        # h_ln2
            pltpu.VMEM((B, T, D), k_cache.dtype),   # kbuf
            pltpu.VMEM((B, T, D), v_cache.dtype),   # vbuf
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    x_out, kc, vc = f(
        t_arr, x, k_cache, v_cache,
        stacks["ln1_s"], stacks["ln1_b"], stacks["ln2_s"], stacks["ln2_b"],
        stacks["qkv_q"], stacks["qkv_s"], stacks["qkv_b"],
        stacks["out_q"], stacks["out_s"], stacks["out_b"],
        stacks["fc_q"], stacks["fc_s"], stacks["fc_b"],
        stacks["proj_q"], stacks["proj_s"], stacks["proj_b"],
    )
    return x_out, kc, vc
