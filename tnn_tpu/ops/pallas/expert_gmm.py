"""Grouped gated feed-forward over rows sorted by expert (Pallas TPU) + the
plain ``jax.numpy`` path.

``nn.moe.ExpertShare`` sorts a step's assignments by expert and pads every
expert's group to whole row tiles, so that a tile of ``tile`` rows belongs
to ONE expert. ``tnn_expert_gmm`` walks the tiles with the tile -> expert map
as scalar prefetch:

    y[tile] = (silu(x W_gate[e]^T) * (x W_up[e]^T)) W_down[e],   e = expert(tile)

The three weights are stored ``(E, F, D)`` (gate and up "out x in", down "in
x out"), so that a block of ``F_BLOCK`` of an expert's ``F`` hidden units is
``F_BLOCK`` contiguous rows of ``D`` in each. Grid ``(tiles, F / F_BLOCK)``:
a step fetches that block of the three weights and adds its share of the
tile's output to a float32 accumulator. An expert with no row has no tile and
is NEVER fetched: the step is bound by the bytes of the experts that got
tokens. Tiles past the live ones are skipped, and their block indices repeat
the last live step's, so the pipeline elides their fetches.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

F_BLOCK = 256       # hidden units of an expert a grid step fetches
# three (F_BLOCK, D) weight blocks, two buffers each, and the row tiles
_VMEM_LIMIT = 40 * 2 ** 20


def row_tile(assignments: int) -> int:
    """Rows of a tile for a step of ``assignments`` (token, expert) pairs:
    one packed bf16 register's 16 sublanes for a decode step, the MXU's 128
    for a chunk."""
    return 16 if assignments <= 1024 else 128


def _kernel(expert_ref, live_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            acc_ref):
    del expert_ref
    i, f, nf = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    @pl.when(i < live_ref[0])
    def _tile():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                      # (tile, D)
        nt = (((1,), (1,)), ((), ()))                       # x @ w^T
        g = jax.lax.dot_general(x, wg_ref[...], nt,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[...], nt,
                                preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)            # (tile, F_BLOCK)
        acc_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _out():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_pallas(x, gate, up, down, tile_expert, live_tiles, tile, interpret):
    m, d = x.shape
    fb = min(F_BLOCK, gate.shape[1])
    nf = gate.shape[1] // fb

    def row_index(i, f, expert, live):
        return (jnp.minimum(i, jnp.maximum(live[0] - 1, 0)), 0)

    def w_index(i, f, expert, live):
        return (expert[i], jnp.where(i < live[0], f, nf - 1), 0)

    w_spec = pl.BlockSpec((None, fb, d), w_index)
    return pl.pallas_call(
        _kernel,
        name="tnn_expert_gmm",          # what the device profile shows
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile, nf),
            in_specs=[pl.BlockSpec((tile, d), row_index),
                      w_spec, w_spec, w_spec],
            out_specs=pl.BlockSpec((tile, d), row_index),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(live_tiles, (1,)).astype(jnp.int32), x, gate, up, down)


def _gmm_xla(x, gate, up, down, tile_expert, live_tiles, tile):
    """The same product in plain ``jax.numpy``, an expert at a time over
    the rows of its tiles (every expert is read: the CPU's path and the
    kernel's parity oracle, not the chip's)."""
    m, d = x.shape
    t = jnp.arange(m // tile)
    of_row = jnp.repeat(jnp.where(t < live_tiles, tile_expert, -1), tile)

    def one(y, e):
        xe = jnp.where((of_row == e)[:, None], x, 0)
        g = jnp.einsum("md,fd->mf", xe, gate[e],
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("md,fd->mf", xe, up[e],
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        return y + jnp.einsum("mf,fd->md", h, down[e],
                              preferred_element_type=jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros((m, d), jnp.float32),
                        jnp.arange(gate.shape[0]))
    return y.astype(x.dtype)


def expert_gmm(x, gate, up, down, tile_expert, live_tiles, *, tile: int,
               backend: str = "auto", interpret: Optional[bool] = None):
    """x (M, D), rows sorted by expert, every expert's group padded to whole
    tiles of ``tile`` rows (padding rows zero); gate / up / down (E, F, D);
    tile_expert (M / tile,) the expert of each tile (past ``live_tiles``:
    the last live tile's); live_tiles: a scalar. Returns (M, D); rows of
    tiles past ``live_tiles`` hold nothing meaningful."""
    if x.shape[0] % tile or gate.shape != up.shape or gate.shape != down.shape:
        raise ValueError(f"x {x.shape} is whole tiles of {tile} rows and "
                         f"the three weights are (E, F, D); got {gate.shape} "
                         f"/ {up.shape} / {down.shape}")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return _gmm_xla(x, gate, up, down, tile_expert, live_tiles, tile)
    if backend != "pallas":
        raise ValueError(f"unknown expert-gmm backend {backend!r}")
    return _gmm_pallas(x, gate, up, down, tile_expert, live_tiles, tile,
                       interpret_default() if interpret is None else interpret)
