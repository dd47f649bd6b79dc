"""Int8 weight-only matmul with in-VMEM dequantization (Pallas TPU kernel).

Why this exists: bs=1 GPT-2 decode is HBM-bandwidth-bound on the WEIGHTS (the
builders measured bf16 decode at ~91% of the bf16 HBM roofline on 2026-07-30;
not measured since: no cell quantizes weights, PERF.md section 4), so the only
route to faster tokens/sec is moving fewer bytes. Storing weights as int8 +
per-output-channel f32 scales halves the bytes; the dequantize happens in VMEM
inside the kernel (XLA cannot fuse a dequant into a dot operand — it
materializes the bf16 weight matrix back to HBM, erasing the saving, which is
why this is a Pallas kernel and not `(q * s) @ x`).

Reference anchor: the never-implemented `CompressionType::QUANTIZATION`
(/root/reference/include/distributed/packet.hpp:10-57) and the fp32-only
inference loop (/root/reference/examples/gpt2_inference.cpp:71-122) — this
exceeds the reference, which ships no quantization at all.

Layout convention: a logical (K, N) matmul weight is stored TRANSPOSED as
``q: (N, K) int8`` with ``scale: (N,) f32`` (absmax/127 per output channel).
That makes the quantization axis the leading one (natural for per-channel
gather/dequant — e.g. the GPT-2 tied embedding (vocab, d) is already in this
layout) and the kernel contracts K on both operands (an "nt" gemm, which the
MXU handles natively). Because the scale is per-N, it factors out of the K
accumulation: out = (x @ q^T) * scale — one multiply per output element, after
the loop.

Padding happens ONCE, at quantize time: ``quantize_int8`` zero-pads the stored
int8 to multiples of 128 on both axes and remembers the logical dims. The
kernel then picks block sizes that exactly divide the stored dims, so the hot
path never pads (an earlier version padded the weight inside the jitted step —
for GPT-2's K=768 with block_k=512 that re-copied every weight through HBM per
decoded token and made int8 SLOWER than bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

# Upper bounds on block sizes (VMEM: x 256x2048x2 + q 1024x2048x1 + acc
# 256x1024x4 + out ≈ 6 MB with double buffering — comfortably inside VMEM).
MAX_BLOCK_M = 256
MAX_BLOCK_N = 1024
MAX_BLOCK_K = 2048


class Int8Weight:
    """A quantized (K, N) matmul weight: ``q`` (N', K') int8, ``scale`` (N',)
    f32, where N'/K' are N/K zero-padded up to multiples of 128 and ``n``/``k``
    are the logical dims.

    Registered as a jax pytree so it can live inside a params tree and cross
    jit boundaries. Decode-time representation only — checkpoints store the
    original float params and quantize after load (tnn_tpu.nn.quant)."""

    def __init__(self, q, scale, n=None, k=None):
        self.q = q
        self.scale = scale
        self.n = int(n) if n is not None else q.shape[0]
        self.k = int(k) if k is not None else q.shape[1]

    @property
    def shape(self):  # logical (K, N), matching the float kernel it replaces
        return (self.k, self.n)

    @property
    def dtype(self):
        return self.q.dtype

    def dequant(self, dtype=jnp.float32):
        """(K, N) float materialization — reference path for tests/fallback."""
        full = self.q.astype(jnp.float32) * self.scale[:, None]
        return full[: self.n, : self.k].T.astype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.n, self.k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n=aux[0], k=aux[1])

    def __repr__(self):
        return f"Int8Weight(K={self.k}, N={self.n})"


jax.tree_util.register_pytree_node_class(Int8Weight)


def _pad_to_multiple(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def quantize_int8(w) -> Int8Weight:
    """Symmetric per-output-channel quantization of a (K, N) weight.

    scale[n] = absmax(w[:, n]) / 127; q[n, k] = round(w[k, n] / scale[n]).
    The stored int8 is zero-padded to multiples of 128 on both axes so the
    matmul kernel never has to pad at run time; padded output channels carry
    scale 1.0 and all-zero rows (their outputs are zero and sliced away).
    """
    w = jnp.asarray(w, jnp.float32)
    k_dim, n_dim = w.shape
    absmax = jnp.max(jnp.abs(w), axis=0)          # (N,)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8).T
    q = _pad_to_multiple(_pad_to_multiple(q, 128, 0), 128, 1)
    scale = _pad_to_multiple(scale, 128, 0, value=1.0)
    return Int8Weight(q, scale, n=n_dim, k=k_dim)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...]                      # (bm, bk) compute dtype
    w = q_ref[...].astype(x.dtype)      # (bn, bk) int8 -> dequant IN VMEM
    acc[:] += jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _final():
        # per-N scale factors out of the K loop: one multiply at the end
        o_ref[...] = (acc[:] * s_ref[...]).astype(o_ref.dtype)


def _block_divisor(size: int, cap: int) -> int:
    """Largest multiple-of-128 divisor of ``size`` (itself a multiple of 128)
    that is <= cap. Falls back to 128, which always divides."""
    c = size // 128
    for b in range(min(cap // 128, c), 0, -1):
        if c % b == 0:
            return 128 * b
    return 128


@functools.partial(jax.jit, static_argnames=("n", "k", "out_dtype"))
def int8_matmul(x, q, scale, *, n: int | None = None, k: int | None = None,
                out_dtype=None):
    """``x @ W`` where W is int8-quantized: x (..., K), q (N', K'), scale (N').

    ``n``/``k`` are W's logical dims (default: q's stored dims). Returns
    (..., n) in ``out_dtype`` (default x.dtype) with f32 accumulation in
    between. Heads pass out_dtype=f32 so logits never round-trip through bf16
    (greedy argmax is sensitive to bf16's 8-bit mantissa). The int8 block is
    dequantized to the compute dtype in VMEM — HBM traffic for the weight is
    K*N bytes instead of bf16's 2*K*N, and the weight is never copied or
    padded inside the step (see module docstring).
    """
    out_dtype = out_dtype or x.dtype
    *lead, k_in = x.shape
    n = q.shape[0] if n is None else n
    k = k_in if k is None else k
    if k_in != k:
        raise ValueError(f"x K dim {k_in} != weight logical K {k}")
    if q.shape[1] < k:
        raise ValueError(f"stored K {q.shape[1]} < logical K {k}")
    # fallback for raw un-padded int8 (direct kernel tests); Int8Weight from
    # quantize_int8 is always pre-padded so this is a no-op on the decode path
    q = _pad_to_multiple(_pad_to_multiple(q, 128, 0), 128, 1)
    scale = _pad_to_multiple(scale, 128, 0, value=1.0)
    np_, kp = q.shape
    m = 1
    for d in lead:
        m *= d
    xf = x.reshape(m, k)

    bm = min(MAX_BLOCK_M, (m + 7) // 8 * 8)
    bn = _block_divisor(np_, MAX_BLOCK_N)
    bk = _block_divisor(kp, MAX_BLOCK_K)
    mp = pl.cdiv(m, bm) * bm

    # x is the small operand (decode: one row per sequence) — padding it is
    # cheap; the weight is untouched
    xf = jnp.pad(xf, ((0, mp - m), (0, kp - k)))
    sp = scale.reshape(1, np_)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=kp // bk),
        name="tnn_quant_matmul",
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, bk), lambda mi, ni, ki: (ni, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(),
    )(xf, q, sp)
    return out[:m, :n].reshape(*lead, n)


def w8a8_matmul(x, w: Int8Weight, out_dtype=None):
    """``x @ W`` via the MXU's NATIVE int8 path: dynamically quantize the
    activation per token (absmax over K), contract int8 x int8 -> int32 with a
    plain ``dot_general`` (XLA lowers this straight onto the MXU — the weight
    streams from HBM as int8, nothing is dequantized or copied), then rescale
    by sx[m] * sw[n].

    This is the decode hot path. A Pallas kernel pays a fixed few-us
    invocation cost; at bs=1 GPT-2 decode that's 49 kernels/token and the
    overhead alone exceeds the int8 bandwidth saving (measured round 4:
    per-layer Pallas matmuls ran at ~3.5-4.7us vs the ~2.2us roofline). XLA's
    int8 dot has no such overhead AND doubles MXU throughput. The added
    activation-quantization error (per-token absmax, ~0.4%/element) is covered
    by the decode benchmark's logits-vs-float verification gate.
    """
    out_dtype = out_dtype or x.dtype
    *lead, k_in = x.shape
    if k_in != w.k:
        raise ValueError(f"x K dim {k_in} != weight logical K {w.k}")
    xf = x.reshape(-1, k_in).astype(jnp.float32)  # rank-stable like int8_matmul
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    sx = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    xi = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    # zero-pad the activation K to the stored (128-multiple) K — zero int8
    # columns contribute nothing; the WEIGHT is never sliced or copied
    pad = w.q.shape[1] - k_in
    if pad:
        xi = jnp.pad(xi, ((0, 0), (0, pad)))
    acc = jax.lax.dot_general(xi, w.q, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * sx * w.scale[None, :]
    return out[:, : w.n].astype(out_dtype).reshape(*lead, w.n)


# Below this many activation rows, per-kernel Pallas overhead beats the
# bandwidth saving and the XLA-native w8a8 path wins; above it (prefill,
# verification forwards) the weight-only in-VMEM-dequant kernel is exact on
# the activation side and the overhead amortizes.
W8A8_MAX_ROWS = 256


def qmatmul(x, w, out_dtype=None):
    """Dispatch ``x @ w``: Int8Weight -> int8 decode paths (w8a8 for small
    activation counts, the in-VMEM-dequant Pallas kernel otherwise); anything
    else -> plain dot_general with f32 accumulation. The single call-site hook
    for layers that want to be quantization-transparent."""
    if isinstance(w, Int8Weight):
        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        if rows <= W8A8_MAX_ROWS:
            return w8a8_matmul(x, w, out_dtype=out_dtype)
        return int8_matmul(x, w.q, w.scale, n=w.n, k=w.k, out_dtype=out_dtype)
    out = jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(out_dtype) if out_dtype is not None else out
