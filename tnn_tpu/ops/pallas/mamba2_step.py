"""A Mamba-2 (state-space duality) layer's recurrent state: a decode step of
it (Pallas TPU) + the plain ``jax.numpy`` forms: the step, and the chunked
scan of a prompt chunk.

A head keeps ``S`` (P, N), head_dim x state, float32. A position reads its
input ``x`` (P,), its step ``dt >= 0``, its log decay ``la = -exp(A_log) dt
<= 0`` and the rows ``B`` and ``C`` (N,) that ALL heads of the layer share
(one group):

    S <- e^la S + (dt x) B^T;   y = S C

(the ``D`` skip, the gate and the norm are the layer's: ``nn.attention.
Mamba2``). Unlike the gated delta rule (``gdn_step``) nothing is read out of
the state before it is written.

``tnn_mamba2_step`` is that for ONE position a row: grid ``(rows, head
groups)``; a step's block is ``HEADS`` heads of a row's state, found by the
row's SLOT (scalar prefetch), aliased in and out: each ``P x N`` state is
read once and written once, nothing else of the state array moves. A row
whose ``snap`` slot is not 0 first copies the state it READ into that slot
of the snapshot array (one DMA from the block just fetched, under
``pl.when``, waited for BEFORE the block is written: ``_kernel`` says why),
as ``gdn_step._kernel`` does and for the same reason
(``serving.kv_pool.StateSlots``).

``dt x`` comes TRANSPOSED, ``(P, heads)``, so that a head's is a column that
broadcasts along the lanes of ``S``; the decays come as rows ``(heads, N)``;
``B`` and ``C`` are one ``(2, N)`` block a row for all its heads. ``y`` is a
lane reduction and leaves as a column. No relayout inside the kernel, no
MXU: the step is bound by the state's bytes.

``ssd_chunk`` is the same recurrence over a chunk of positions in closed
form (with ``s_t`` the running sum of ``la``):

    y_t = sum_{r<=t} e^(s_t - s_r) (C_t . B_r) dt_r x_r  +  e^(s_t) S_0 C_t
    S_Q = e^(s_Q) S_0 + sum_r e^(s_Q - s_r) dt_r x_r B_r^T

as matmuls over sub-chunks of ``SUB`` positions and a ``lax.scan`` between
them, for prompt chunks. Every exponent is a decay since an EARLIER position,
so none is positive whatever the sub-chunk's length. A position with ``dt =
0`` (so ``la = 0``) leaves the state as it was: how a step's padding is kept
out of it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

HEADS = 16      # heads of a row a grid step holds (512 KiB of 64 x 128 states)
SUB = 64        # positions a closed-form sub-chunk of ``ssd_chunk`` covers
HIGHEST = jax.lax.Precision.HIGHEST
# two buffers of the state block in and out, the small operands
_VMEM_LIMIT = 24 * 2 ** 20


def _kernel(slots_ref, snaps_ref, dx_ref, dec_ref, bc_ref, rec_ref, snap_in,
            o_ref, rec_out, snap_out, sem, *, layer, hb):
    del slots_ref, snap_in
    b, hg = pl.program_id(0), pl.program_id(1)
    snap = snaps_ref[b]
    keep = pltpu.make_async_copy(
        rec_ref, snap_out.at[pl.ds(layer, 1), pl.ds(snap, 1),
                             pl.ds(hg * hb, hb)], sem)

    # the copy is DONE before the block is computed: on the chip a write of
    # ``rec_out`` reaches the buffer the DMA reads (the aliased state's in
    # and out windows are one), and a copy left in flight took the first two
    # heads of a block half updated (PR 49, ``chip_smoke.py``'s snapshot line;
    # the interpreter keeps two buffers and cannot show it). One row in
    # ``SNAPSHOT_EVERY`` pays for it, half a microsecond a block
    @pl.when(snap > 0)
    def _keep():
        keep.start()
        keep.wait()

    b_row, c_row = bc_ref[0:1, :], bc_ref[1:2, :]       # (1, N)
    for j in range(hb):
        dx = dx_ref[:, j:j + 1]                         # (P, 1)
        decay = dec_ref[j:j + 1, :]                     # (1, N)
        s = rec_ref[0, 0, j] * decay + dx * b_row       # (P, N)
        o_ref[:, j:j + 1] = jnp.sum(s * c_row, axis=1, keepdims=True)
        rec_out[0, 0, j] = s


def _step_pallas(x, dt, la, bm, cm, rec, snap, slots, snaps, layer,
                 interpret):
    b, h, p = x.shape
    n = bm.shape[-1]
    hb = HEADS if h % HEADS == 0 else h
    groups = h // hb
    # (B, H, P) -> (B, groups, P, hb): a head's ``dt x`` a column
    dx = (x * dt[..., None]).reshape(b, groups, hb, p).transpose(0, 1, 3, 2)
    dec = jnp.broadcast_to(jnp.exp(la).reshape(b, groups, hb, 1),
                           (b, groups, hb, n))
    bc = jnp.stack([bm, cm], axis=1)                    # (B, 2, N)

    def small(i, j, slots, snaps):
        return (i, j, 0, 0)

    def shared(i, j, slots, snaps):
        return (i, 0, 0)

    def state(i, j, slots, snaps):
        return (layer, slots[i], j, 0, 0)

    rec_spec = pl.BlockSpec((1, 1, hb, p, n), state)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    o, rec, snap = pl.pallas_call(
        functools.partial(_kernel, layer=layer, hb=hb),
        name="tnn_mamba2_step",         # what the device profile shows
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[pl.BlockSpec((None, None, p, hb), small),
                      pl.BlockSpec((None, None, hb, n), small),
                      pl.BlockSpec((None, 2, n), shared),
                      rec_spec, any_spec],
            out_specs=[pl.BlockSpec((None, None, p, hb), small),
                       rec_spec, any_spec],
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((b, groups, p, hb), jnp.float32),
                   jax.ShapeDtypeStruct(rec.shape, rec.dtype),
                   jax.ShapeDtypeStruct(snap.shape, snap.dtype)],
        # operands count the two prefetched arrays: rec is 5, snap 6
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(slots.astype(jnp.int32), snaps.astype(jnp.int32), dx, dec, bc, rec,
      snap)
    return o.transpose(0, 1, 3, 2).reshape(b, h, p), rec, snap


def step_math(s, x, dt, la, bm, cm):
    """One position of the recurrence on states ``s`` (..., H, P, N): (y
    (..., H, P), the new states). x (..., H, P); dt, la (..., H); bm, cm
    (..., N), shared by the heads."""
    s = s * jnp.exp(la)[..., None, None] \
        + (x * dt[..., None])[..., None] * bm[..., None, None, :]
    return jnp.sum(s * cm[..., None, None, :], axis=-1), s


def _step_xla(x, dt, la, bm, cm, rec, snap, slots, snaps, layer):
    s0 = rec[layer, slots]
    # rows that keep nothing write the dump slot 0, which nobody reads
    snap = snap.at[layer, snaps].set(s0)
    y, s1 = step_math(s0, x, dt, la, bm, cm)
    return y, rec.at[layer, slots].set(s1), snap


def mamba2_step(x, dt, la, bm, cm, rec, snap, slots, snaps, *, layer: int,
                backend: str = "auto", interpret: Optional[bool] = None):
    """One position a row against the live states. x (B, H, P), dt, la (B,
    H), bm, cm (B, N), all float32; ``rec`` (L, S, H, P, N) the live states
    and ``snap`` (L, S', H, P, N) the snapshots, float32; ``slots`` (B,) each
    row's slot of ``rec`` (0: the scratch slot of a padding row), ``snaps``
    (B,) the slot of ``snap`` that takes the state the row READ (0: none).
    Returns (y (B, H, P), rec, snap); donated through jit both arrays are
    updated in place."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return _step_xla(x, dt, la, bm, cm, rec, snap, slots, snaps, layer)
    if backend != "pallas":
        raise ValueError(f"unknown mamba2-step backend {backend!r}")
    return _step_pallas(x, dt, la, bm, cm, rec, snap, slots, snaps, layer,
                        interpret_default() if interpret is None
                        else interpret)


def ssd_chunk(x, dt, la, bm, cm, s0, sub: int = SUB):
    """``Q`` positions of the recurrence in closed form. x (B, Q, H, P), dt,
    la (B, Q, H), bm, cm (B, Q, N), s0 (B, H, P, N), float32; ``Q`` a
    multiple of ``sub`` or less than it. Returns (y (B, Q, H, P), the states
    after the last position)."""
    b, qw, h, p = x.shape
    c = min(sub, qw)
    if qw % c:
        raise ValueError(f"a chunk of {qw} is no whole sub-chunks of {c}")
    n = qw // c

    def heads(t):               # (B, Q, H, ...) -> (n, B, H, c, ...)
        return t.reshape((b, n, c, h) + t.shape[3:]).transpose(
            (1, 0, 3, 2) + tuple(range(4, t.ndim + 1)))

    def rows(t):                # (B, Q, N) -> (n, B, c, N)
        return t.reshape(b, n, c, -1).transpose(1, 0, 2, 3)

    upto = jnp.tril(jnp.ones((c, c), bool))
    mm = functools.partial(jnp.einsum, precision=HIGHEST)

    def one(s, xs):
        dx, lac, bc, cc = xs
        run = jnp.cumsum(lac, axis=-1)                  # (B, H, c)
        gap = run[..., :, None] - run[..., None, :]     # [t, r]: r -> t
        mix = jnp.exp(jnp.where(upto, gap, -jnp.inf)) \
            * mm("btn,brn->btr", cc, bc)[:, None]
        y = mm("bhtr,bhrp->bhtp", mix, dx) \
            + jnp.exp(run)[..., None] * mm("bhpn,btn->bhtp", s, cc)
        left = jnp.exp(run[..., -1:] - run)             # r -> the end
        s = jnp.exp(run[..., -1])[..., None, None] * s \
            + mm("bhrp,brn->bhpn", dx * left[..., None], bc)
        return s, y

    s1, y = jax.lax.scan(one, s0, (heads(x * dt[..., None]), heads(la),
                                   rows(bm), rows(cm)))
    # (n, B, H, c, P) -> (B, Q, H, P)
    return y.transpose(1, 0, 3, 2, 4).reshape(b, qw, h, p), s1
