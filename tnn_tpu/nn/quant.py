"""Post-training weight-only int8 quantization for decode.

``quantize_for_decode(params)`` walks a params pytree and replaces every large
2-D matmul kernel with an ``Int8Weight`` (per-output-channel symmetric int8 +
f32 scales, ops/pallas/quant_matmul.py). Layers are quantization-transparent:
Dense / MultiHeadAttention / Embedding route Int8Weight params through the
in-VMEM-dequant Pallas kernel and float params through the normal dot.

Decode is HBM-bound on weight bytes (the builders' figure of 2026-07-30: bf16
decode at ~91% of the bf16 roofline; not measured since, PERF.md section 4 has
no cell with quantized weights), so halving weight bytes is the one lever
below it. This is
inference-time only: checkpoints store float params; quantize after load.
Optimizers cannot step Int8Weight params.

What gets quantized (and what doesn't):
  * keys named kernel / qkv_kernel / out_kernel with ndim==2 and both dims
    >= 128 (projections, MLPs, untied heads);
  * the token embedding ``wte.table`` — it is matmul'd by the tied head every
    step and is GPT-2's single largest weight; lookups gather+dequant rows;
  * NOT positional tables (sliced, not matmul'd), norms, biases, or anything
    small enough that quantization saves no meaningful bandwidth.

Exceeds the reference, whose QUANTIZATION enum is declared but never
implemented (include/distributed/packet.hpp:10-57).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp

from ..ops.pallas.quant_matmul import Int8Weight, quantize_int8

_MATMUL_KEYS = ("kernel", "qkv_kernel", "out_kernel")


def _default_predicate(path: Tuple[str, ...], leaf) -> bool:
    if getattr(leaf, "ndim", 0) != 2 or not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    if min(leaf.shape) < 128:
        return False  # bandwidth saving is negligible; keep exact
    if path[-1] in _MATMUL_KEYS:
        return True
    # token-embedding table used by the tied softmax head (GPT-2's "wte");
    # positional tables are position-sliced, never matmul'd — keep float
    return path[-1] == "table" and any("wte" in p for p in path[:-1])


def quantize_for_decode(params: Any,
                        predicate: Optional[Callable[..., bool]] = None,
                        _path: Tuple[str, ...] = ()) -> Any:
    """Return a copy of ``params`` with selected kernels as Int8Weight.

    ``predicate(path, leaf) -> bool`` overrides the default selection. The
    embedding table is quantized ROW-wise (per vocab entry), matmul kernels
    per OUTPUT channel — both are the leading axis of the stored (N, K) int8.
    """
    pred = predicate or _default_predicate
    if isinstance(params, dict):
        return {k: quantize_for_decode(v, pred, _path + (k,))
                for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        t = type(params)
        return t(quantize_for_decode(v, pred, _path + (str(i),))
                 for i, v in enumerate(params))
    if isinstance(params, Int8Weight) or not pred(_path, params):
        return params
    if _path[-1] == "table":
        # (vocab, dim) with per-row scale IS the kernel's (N, K) layout for
        # the tied head x @ table.T; quantize_int8 expects (K, N), so feed the
        # transpose — its output q == table quantized rows
        return quantize_int8(jnp.asarray(params).T)
    return quantize_int8(params)


def quantized_bytes(params: Any) -> int:
    """Total bytes of the params tree as stored (diagnostic for HBM-fit /
    bandwidth statements in benchmarks)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
