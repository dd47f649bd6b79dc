"""The gated delta rule's recurrent state, a decode step of it (Pallas TPU) +
the plain ``jax.numpy`` forms: the step, and the chunked scan of a prompt
chunk.

A value head keeps ``S`` (Dk, Dv), key x value, float32. A position reads
its query, key and value (``q``, ``k`` L2-normed, ``q`` scaled), its decay
``g <= 0`` and its write strength ``beta``:

    S <- e^g S;  r = S^T k;  d = beta (v - r);  S <- S + k d^T;  o = S^T q

``tnn_gdn_step`` is that for ONE position a row: grid ``(rows, head
groups)``; a step's block is ``HEADS`` heads of a row's state, found by the
row's SLOT (scalar prefetch), aliased in and out: each ``Dk x Dv`` state is
read once and written once, nothing else of the state array moves. A row
whose ``snap`` slot is not 0 also copies the state it READ into that slot of
the snapshot array (one DMA from the block just fetched, under ``pl.when``):
how the engine keeps the state of an earlier position while the device runs
ahead of the commit (``serving.kv_pool.StateSlots``).

Keys and queries come TRANSPOSED, ``(Dk, heads)``, so that a head's is a
column that broadcasts along the lanes of ``S``; values, decays and betas
come as rows ``(heads, Dv)``. No relayout inside the kernel, no MXU: the
step is bound by the state's bytes.

``gdn_chunk`` is the same recurrence over a chunk of positions in closed
form (the WY representation: a unit lower-triangular solve a sub-chunk of
``SUB`` positions, a ``lax.scan`` between sub-chunks), for prompt chunks. A
position with ``g = 0`` and ``beta = 0`` leaves the state as it was: how a
step's padding is kept out of it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import interpret_default

HEADS = 8       # value heads of a row a grid step holds (512 KiB of state)
SUB = 16        # positions a closed-form sub-chunk of ``gdn_chunk`` covers
HIGHEST = jax.lax.Precision.HIGHEST
# two buffers of the state block in and out, the small operands
_VMEM_LIMIT = 24 * 2 ** 20


def _kernel(slots_ref, snaps_ref, qk_ref, veb_ref, rec_ref, snap_in, o_ref,
            rec_out, snap_out, sem, *, layer, hb):
    del slots_ref, snap_in
    b, hg = pl.program_id(0), pl.program_id(1)
    snap = snaps_ref[b]
    keep = pltpu.make_async_copy(
        rec_ref, snap_out.at[pl.ds(layer, 1), pl.ds(snap, 1),
                             pl.ds(hg * hb, hb)], sem)

    @pl.when(snap > 0)
    def _start():
        keep.start()

    for j in range(hb):
        qc = qk_ref[:, j:j + 1]                         # (Dk, 1)
        kc = qk_ref[:, hb + j:hb + j + 1]
        v = veb_ref[j:j + 1, :]                         # (1, Dv)
        decay = veb_ref[hb + j:hb + j + 1, :]
        beta = veb_ref[2 * hb + j:2 * hb + j + 1, :]
        s = rec_ref[0, 0, j] * decay                          # (Dk, Dv)
        r = jnp.sum(s * kc, axis=0, keepdims=True)
        s = s + kc * (beta * (v - r))
        o_ref[j:j + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        rec_out[0, 0, j] = s

    @pl.when(snap > 0)
    def _wait():
        keep.wait()


def _step_pallas(q, k, v, g, beta, rec, snap, slots, snaps, layer,
                 interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = HEADS if h % HEADS == 0 else h
    n = h // hb

    def cols(x):                # (B, H, Dk) -> (B, n, Dk, hb)
        return x.reshape(b, n, hb, dk).transpose(0, 1, 3, 2)

    def rows(x):                # (B, H) -> (B, n, hb, Dv)
        return jnp.broadcast_to(x.reshape(b, n, hb, 1), (b, n, hb, dv))

    qk = jnp.concatenate([cols(q), cols(k)], axis=-1)
    veb = jnp.concatenate([v.reshape(b, n, hb, dv), rows(jnp.exp(g)),
                           rows(beta)], axis=2)

    def small(i, j, slots, snaps):
        return (i, j, 0, 0)

    def state(i, j, slots, snaps):
        return (layer, slots[i], j, 0, 0)

    rec_spec = pl.BlockSpec((1, 1, hb, dk, dv), state)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    o, rec, snap = pl.pallas_call(
        functools.partial(_kernel, layer=layer, hb=hb),
        name="tnn_gdn_step",            # what the device profile shows
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n),
            in_specs=[pl.BlockSpec((None, None, dk, 2 * hb), small),
                      pl.BlockSpec((None, None, 3 * hb, dv), small),
                      rec_spec, any_spec],
            out_specs=[pl.BlockSpec((None, None, hb, dv), small),
                       rec_spec, any_spec],
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((b, n, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct(rec.shape, rec.dtype),
                   jax.ShapeDtypeStruct(snap.shape, snap.dtype)],
        # operands count the two prefetched arrays: rec is 4, snap 5
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(slots.astype(jnp.int32), snaps.astype(jnp.int32), qk, veb, rec, snap)
    return o.reshape(b, h, dv), rec, snap


def step_math(s, q, k, v, g, beta):
    """One position of the rule on states ``s`` (..., Dk, Dv): (o (..., Dv),
    the new states). q, k (..., Dk); v (..., Dv); g, beta (...)."""
    s = s * jnp.exp(g)[..., None, None]
    r = jnp.sum(s * k[..., :, None], axis=-2)
    s = s + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def _step_xla(q, k, v, g, beta, rec, snap, slots, snaps, layer):
    s0 = rec[layer, slots]
    # rows that keep nothing write the dump slot 0, which nobody reads
    snap = snap.at[layer, snaps].set(s0)
    o, s1 = step_math(s0, q, k, v, g, beta)
    return o, rec.at[layer, slots].set(s1), snap


def gdn_step(q, k, v, g, beta, rec, snap, slots, snaps, *, layer: int,
             backend: str = "auto", interpret: Optional[bool] = None):
    """One position a row against the live states. q, k (B, H, Dk), v (B, H,
    Dv), g, beta (B, H), all float32, a value head each (a key head's q and
    k repeated for its value heads); ``rec`` (L, S, H, Dk, Dv) the live
    states and ``snap`` (L, S', H, Dk, Dv) the snapshots, float32; ``slots``
    (B,) each row's slot of ``rec`` (0: the scratch slot of a padding row),
    ``snaps`` (B,) the slot of ``snap`` that takes the state the row READ
    (0: none). Returns (o (B, H, Dv), rec, snap); donated through jit both
    arrays are updated in place."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return _step_xla(q, k, v, g, beta, rec, snap, slots, snaps, layer)
    if backend != "pallas":
        raise ValueError(f"unknown gdn-step backend {backend!r}")
    return _step_pallas(q, k, v, g, beta, rec, snap, slots, snaps, layer,
                        interpret_default() if interpret is None
                        else interpret)


def gdn_chunk(q, k, v, g, beta, s0, sub: int = SUB):
    """``Q`` positions of the rule in closed form. q, k (B, Q, H, Dk), v (B,
    Q, H, Dv), g, beta (B, Q, H), s0 (B, H, Dk, Dv), float32; ``Q`` a
    multiple of ``sub`` or less than it. Returns (o (B, Q, H, Dv), the
    states after the last position)."""
    b, qw, h, dk = q.shape
    dv = v.shape[-1]
    c = min(sub, qw)
    if qw % c:
        raise ValueError(f"a chunk of {qw} is no whole sub-chunks of {c}")
    n = qw // c

    def split(x):               # (B, Q, H, .) -> (n, B, H, c, .)
        return x.reshape((b, n, c, h) + x.shape[3:]).transpose(
            (1, 0, 3, 2) + tuple(range(4, x.ndim + 1)))

    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    upto = jnp.tril(jnp.ones((c, c), bool))
    eye = jnp.eye(c, dtype=jnp.float32)
    mm = functools.partial(jnp.einsum, precision=HIGHEST)

    def one(s, xs):
        qc, kc, vc, gc, bc = xs
        run = jnp.cumsum(gc, axis=-1)                   # (B, H, c)
        gam = jnp.exp(run)          # the decay since the sub-chunk's start
        gap = run[..., :, None] - run[..., None, :]     # [s, r]: r -> s
        a = bc[..., None] * jnp.exp(jnp.where(strict, gap, -jnp.inf)) \
            * mm("bhsd,bhrd->bhsr", kc, kc)
        rhs = jnp.concatenate([bc[..., None] * vc,
                               (bc * gam)[..., None] * kc], axis=-1)
        sol = jax.scipy.linalg.solve_triangular(
            eye + a, rhs, lower=True, unit_diagonal=True)
        d = sol[..., :dv] - mm("bhck,bhkv->bhcv", sol[..., dv:], s)
        att = mm("bhtd,bhsd->bhts", qc, kc) \
            * jnp.exp(jnp.where(upto, gap, -jnp.inf))
        o = gam[..., None] * mm("bhtk,bhkv->bhtv", qc, s) \
            + mm("bhts,bhsv->bhtv", att, d)
        left = jnp.exp(run[..., -1:] - run)             # s -> the end
        s = gam[..., -1][..., None, None] * s \
            + mm("bhsk,bhsv->bhkv", kc * left[..., None], d)
        return s, o

    s1, o = jax.lax.scan(one, s0, (split(q), split(k), split(v), split(g),
                                   split(beta)))
    # (n, B, H, c, Dv) -> (B, Q, H, Dv)
    return o.transpose(1, 0, 3, 2, 4).reshape(b, qw, h, dv), s1
