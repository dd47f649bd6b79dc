"""Parity tests for the ragged paged-attention decode kernel.

The Pallas kernel (``ops/pallas/paged_attention``) runs in interpret mode on
CPU (forced by the ``kernel`` marker's conftest fixture), checked against the
XLA-lax reference in the same module; the reference itself is checked against
a dense softmax-attention oracle built here. Covers ragged lengths, block
sizes, GQA head ratios, layer selection, zero-length rows, and the
``scatter_kv_rows`` write half of the page contract. PACKED pages (``p`` KV
heads side by side in a page row, ``pa.lane_pack``) are cases of the same
tests: the pool is made unpacked, ``_pack`` lays it out as ``PagedKVPool``
would, and every oracle reads the unpacked one.
"""
import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tnn_tpu.ops.pallas import paged_attention as pa

pytestmark = pytest.mark.kernel


def _pack(pages, p):
    """(..., H_kv, bs, Dh) as a pool rests with ``p`` heads a row:
    (..., H_kv / p, bs, p * Dh), heads ``p j .. p j + p - 1`` side by side."""
    *lead, hkv, bs, dh = pages.shape
    return pages.reshape(*lead, hkv // p, p, bs, dh).swapaxes(-3, -2) \
        .reshape(*lead, hkv // p, bs, p * dh)


def _random_case(seed, *, num_layers=2, num_blocks=12, block_size=8,
                 num_heads=4, num_kv_heads=2, head_dim=16, batch=3,
                 blocks_per_row=3, dtype=jnp.float32):
    """Random pool pages + block tables with ragged per-row lengths.

    Block 0 plays the pool's reserved-scratch role: live tables draw from
    blocks 1.., and rows' table tails are padded with 0 like the engine does.
    """
    rng = np.random.default_rng(seed)
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    pages_k = jnp.asarray(rng.normal(size=shape), dtype)
    pages_v = jnp.asarray(rng.normal(size=shape), dtype)
    need = batch * blocks_per_row
    assert need <= num_blocks - 1, "test geometry: not enough live blocks"
    perm = rng.permutation(np.arange(1, num_blocks))[:need]
    tables = perm.reshape(batch, blocks_per_row).astype(np.int32)
    # ragged: one short row, one full row, one mid row ending mid-block
    lens = rng.integers(1, blocks_per_row * block_size + 1, size=batch)
    lens[0] = 1
    lens[-1] = blocks_per_row * block_size
    # dead trailing table entries point at scratch, as the engine pads them
    for i in range(batch):
        nb_live = math.ceil(lens[i] / block_size)
        tables[i, nb_live:] = 0
    q = jnp.asarray(rng.normal(size=(batch, num_heads, head_dim)), dtype)
    return q, pages_k, pages_v, jnp.asarray(tables), jnp.asarray(
        lens, jnp.int32)


def _dense_oracle(q, pages_k, pages_v, tables, lens, layer):
    """Plain-numpy masked softmax attention — independent of the module."""
    q = np.asarray(q, np.float32)
    k = np.asarray(pages_k[layer], np.float32)[np.asarray(tables)]
    v = np.asarray(pages_v[layer], np.float32)[np.asarray(tables)]
    b, nb, hkv, bs, dh = k.shape
    h = q.shape[1]
    g = h // hkv
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, dh)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, dh)
    out = np.zeros_like(q)
    for i in range(b):
        n = int(lens[i])
        for qh in range(h):
            kh = qh // g
            if n == 0:
                continue
            s = k[i, kh, :n] @ q[i, qh] / math.sqrt(dh)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, qh] = p @ v[i, kh, :n]
    return out


@pytest.mark.parametrize("block_size", [4, 8])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (4, 1)],
                         ids=["mha", "gqa2", "mqa"])
def test_kernel_matches_reference_ragged(block_size, heads):
    h, hkv = heads
    q, pk, pv, tables, lens = _random_case(
        block_size * 10 + h, block_size=block_size, num_heads=h,
        num_kv_heads=hkv)
    for layer in range(pk.shape[0]):
        ref = pa.paged_attention_reference(q, pk, pv, tables, lens,
                                           layer=layer)
        out = pa.paged_attention(q, pk, pv, tables, lens, layer=layer,
                                 backend="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_reference_matches_dense_oracle():
    q, pk, pv, tables, lens = _random_case(7)
    for layer in range(pk.shape[0]):
        ref = pa.paged_attention_reference(q, pk, pv, tables, lens,
                                           layer=layer)
        oracle = _dense_oracle(q, pk, pv, tables, lens, layer)
        np.testing.assert_allclose(np.asarray(ref), oracle, atol=1e-5,
                                   rtol=1e-5)


def test_zero_length_rows_output_zero():
    q, pk, pv, tables, lens = _random_case(11)
    lens = lens.at[0].set(0).at[2].set(0)
    for backend in ("pallas", "xla"):
        out = pa.paged_attention(q, pk, pv, tables, lens, backend=backend)
        assert np.all(np.asarray(out[0]) == 0), backend
        assert np.all(np.asarray(out[2]) == 0), backend
        np.testing.assert_allclose(
            np.asarray(out[1]),
            _dense_oracle(q, pk, pv, tables, lens, 0)[1],
            atol=2e-5, rtol=2e-5)


def test_single_token_rows():
    """kv_len == 1 everywhere: attention is the identity over the one row."""
    q, pk, pv, tables, _ = _random_case(13)
    lens = jnp.ones((q.shape[0],), jnp.int32)
    out = pa.paged_attention(q, pk, pv, tables, lens, backend="pallas")
    oracle = _dense_oracle(q, pk, pv, tables, lens, 0)
    np.testing.assert_allclose(np.asarray(out), oracle, atol=2e-5, rtol=2e-5)


def test_single_layer_pages_and_bf16():
    q, pk, pv, tables, lens = _random_case(17, dtype=jnp.bfloat16)
    out = pa.paged_attention(q, pk[0], pv[0], tables, lens, backend="pallas")
    ref = pa.paged_attention_reference(q, pk[0], pv[0], tables, lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_scatter_kv_rows_roundtrip():
    rng = np.random.default_rng(3)
    q, pk, pv, tables, lens = _random_case(19)
    b, h_kv, bs, dh = q.shape[0], pk.shape[2], pk.shape[3], pk.shape[4]
    rows = jnp.asarray(rng.normal(size=(b, h_kv, dh)), jnp.float32)
    offsets = lens - 1  # write at each row's last live position
    pk2 = pa.scatter_kv_rows(pk, tables, offsets, rows, layer=1)
    for i in range(b):
        blk = int(tables[i, int(offsets[i]) // bs])
        slot = int(offsets[i]) % bs
        np.testing.assert_array_equal(np.asarray(pk2[1, blk, :, slot, :]),
                                      np.asarray(rows[i]))
    # layer 0 untouched
    np.testing.assert_array_equal(np.asarray(pk2[0]), np.asarray(pk[0]))
    # 4-D single-layer form
    pk1 = pa.scatter_kv_rows(pk[0], tables, offsets, rows)
    blk0 = int(tables[0, int(offsets[0]) // bs])
    np.testing.assert_array_equal(
        np.asarray(pk1[blk0, :, int(offsets[0]) % bs, :]),
        np.asarray(rows[0]))


def test_jit_and_traced_layer_index():
    """The engine traces layer as a loop-carried python int, but the kernel
    must also accept it traced (scalar-prefetch operand)."""
    q, pk, pv, tables, lens = _random_case(23)

    @jax.jit
    def run(q, pk, pv, tables, lens, layer):
        return pa.paged_attention(q, pk, pv, tables, lens, layer=layer,
                                  backend="pallas")

    for layer in range(pk.shape[0]):
        out = run(q, pk, pv, tables, lens, jnp.asarray(layer, jnp.int32))
        ref = pa.paged_attention_reference(q, pk, pv, tables, lens,
                                           layer=layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_arg_validation():
    q, pk, pv, tables, lens = _random_case(29)
    with pytest.raises(ValueError, match="kv heads"):
        pa.paged_attention(q[:, :3], pk, pv, tables, lens)
    with pytest.raises(ValueError, match="batch"):
        pa.paged_attention(q, pk, pv, tables[:2], lens)
    with pytest.raises(ValueError, match="backend"):
        pa.paged_attention(q, pk, pv, tables, lens, backend="cuda")
    with pytest.raises(ValueError, match="layer is required"):
        pa.scatter_kv_rows(pk, tables, lens - 1,
                           jnp.zeros((3, 2, 16)))
    with pytest.raises(ValueError, match="q_lens"):
        pa.paged_attention(q, pk, pv, tables, lens, q_lens=lens)
    with pytest.raises(ValueError, match="layer is required"):
        pa.scatter_kv_chunk(pk, tables, lens - 1, jnp.zeros((3, 4, 2, 16)),
                            jnp.ones((3,), jnp.int32))


# -- ragged multi-token query chunks (chunked prefill) ------------------------


def _random_chunk_case(seed, *, num_layers=2, num_blocks=16, block_size=8,
                       num_heads=4, num_kv_heads=2, head_dim=16, batch=4,
                       blocks_per_row=3, qw=4, dtype=jnp.float32):
    """Random pool history + a ragged chunk per row: row i has ``starts[i]``
    previously written positions and ``q_lens[i]`` new tokens this step
    (0 = absent padding row, 1 = decode-like, up to the full chunk width)."""
    rng = np.random.default_rng(seed)
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_dim)
    pages_k = jnp.asarray(rng.normal(size=shape), dtype)
    pages_v = jnp.asarray(rng.normal(size=shape), dtype)
    need = batch * blocks_per_row
    assert need <= num_blocks - 1, "test geometry: not enough live blocks"
    perm = rng.permutation(np.arange(1, num_blocks))[:need]
    tables = perm.reshape(batch, blocks_per_row).astype(np.int32)
    cap = blocks_per_row * block_size
    q_lens = rng.integers(0, qw + 1, size=batch)
    q_lens[0] = 0            # absent row: must output exactly 0
    q_lens[1] = 1            # decode-like row inside the chunked launch
    q_lens[-1] = qw          # full chunk
    starts = np.array([int(rng.integers(0, cap - ql + 1))
                       for ql in q_lens], np.int32)
    kv_lens = starts + q_lens
    for i in range(batch):
        nb_live = max(1, math.ceil(max(int(kv_lens[i]), 1) / block_size))
        tables[i, nb_live:] = 0
    q = jnp.asarray(rng.normal(size=(batch, qw, num_heads, head_dim)), dtype)
    rows_k = jnp.asarray(rng.normal(size=(batch, qw, num_kv_heads, head_dim)),
                         dtype)
    rows_v = jnp.asarray(rng.normal(size=(batch, qw, num_kv_heads, head_dim)),
                         dtype)
    return (q, pages_k, pages_v, jnp.asarray(tables),
            jnp.asarray(starts, jnp.int32), jnp.asarray(q_lens, jnp.int32),
            rows_k, rows_v)


def _dense_oracle_mq(q, pages_k, pages_v, tables, kv_lens, q_lens, layer):
    """Numpy oracle for the ragged-chunk form: chunk token t sits at absolute
    position kv_lens - q_lens + t and attends causally over everything up to
    and including itself; dead tokens (t >= q_lens) output exactly 0."""
    q = np.asarray(q, np.float32)
    k = np.asarray(pages_k[layer], np.float32)[np.asarray(tables)]
    v = np.asarray(pages_v[layer], np.float32)[np.asarray(tables)]
    b, nb, hkv, bs, dh = k.shape
    qw, h = q.shape[1], q.shape[2]
    g = h // hkv
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, dh)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * bs, dh)
    out = np.zeros_like(q)
    for i in range(b):
        n, ql = int(kv_lens[i]), int(q_lens[i])
        for t in range(ql):
            m = n - ql + t + 1   # keys visible to chunk token t (causal)
            if m <= 0:
                continue
            for qh in range(h):
                kh = qh // g
                s = k[i, kh, :m] @ q[i, t, qh] / math.sqrt(dh)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, t, qh] = p @ v[i, kh, :m]
    return out


@pytest.mark.parametrize("block_size", [4, 8])
@pytest.mark.parametrize(
    "heads", [(4, 4, 1), (4, 2, 1), (4, 1, 1), (4, 4, 2), (4, 4, 4),
              (4, 2, 2)],
    ids=["mha", "gqa2", "mqa", "mha-p2", "mha-p4", "gqa2-p2"])
@pytest.mark.parametrize("qw", [4, 8])
def test_multitoken_kernel_matches_oracle(block_size, heads, qw):
    """Ragged q chunks x GQA ratios x block sizes x heads a page row: the
    kernel, the XLA reference, and the dense oracle agree; scatter_kv_chunk
    writes the chunk's KV where attention then reads it, and into a packed
    page exactly what it writes into an unpacked one."""
    h, hkv, p = heads
    q, pk, pv, tables, starts, q_lens, rows_k, rows_v = _random_chunk_case(
        block_size * 100 + h * 10 + qw, block_size=block_size, num_heads=h,
        num_kv_heads=hkv, qw=qw)
    kv_lens = starts + q_lens
    flat_k = pa.scatter_kv_chunk(pk, tables, starts, rows_k, q_lens, layer=1)
    flat_v = pa.scatter_kv_chunk(pv, tables, starts, rows_v, q_lens, layer=1)
    pk = pa.scatter_kv_chunk(_pack(pk, p), tables, starts, rows_k, q_lens,
                             layer=1)
    pv = pa.scatter_kv_chunk(_pack(pv, p), tables, starts, rows_v, q_lens,
                             layer=1)
    assert pk.shape[2:] == (hkv // p, block_size, p * q.shape[-1])
    _assert_same_but_scratch(pk, _pack(flat_k, p))
    ref = pa.paged_attention_reference(q, pk, pv, tables, kv_lens,
                                       q_lens=q_lens, layer=1)
    out = pa.paged_attention(q, pk, pv, tables, kv_lens, q_lens=q_lens,
                             layer=1, backend="pallas")
    oracle = _dense_oracle_mq(q, flat_k, flat_v, tables, kv_lens, q_lens, 1)
    np.testing.assert_allclose(np.asarray(ref), oracle, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # dead rows (q_lens 0 / t >= q_lens) are exactly 0, not just close
    assert np.all(np.asarray(out[0]) == 0)
    ql = np.asarray(q_lens)
    for i in range(q.shape[0]):
        assert np.all(np.asarray(out[i, ql[i]:]) == 0), i


def test_multitoken_q1_matches_decode_form():
    """A chunked launch with every row at q_len 1 must reproduce the legacy
    decode form bit-for-bit (same kernel geometry, same mask)."""
    q3, pk, pv, tables, lens = _random_case(31)
    dec = pa.paged_attention(q3, pk, pv, tables, lens, backend="pallas")
    mq = pa.paged_attention(q3[:, None], pk, pv, tables, lens,
                            q_lens=jnp.ones_like(lens), backend="pallas")
    assert mq.shape == (q3.shape[0], 1) + q3.shape[1:]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(mq[:, 0]))
    ref_dec = pa.paged_attention_reference(q3, pk, pv, tables, lens)
    ref_mq = pa.paged_attention_reference(q3[:, None], pk, pv, tables, lens,
                                          q_lens=jnp.ones_like(lens))
    np.testing.assert_array_equal(np.asarray(ref_dec),
                                  np.asarray(ref_mq[:, 0]))


def test_scatter_kv_chunk_roundtrip_and_scratch_only():
    """Live chunk tokens land at table[pos // bs] slot pos % bs; dead tokens
    write ONLY the reserved scratch block 0; other layers untouched."""
    q, pk, pv, tables, starts, q_lens, rows_k, _ = _random_chunk_case(37)
    bs = pk.shape[3]
    pk2 = pa.scatter_kv_chunk(pk, tables, starts, rows_k, q_lens, layer=1)
    b, qw = rows_k.shape[:2]
    live_slots = set()
    for i in range(b):
        for t in range(int(q_lens[i])):
            pos = int(starts[i]) + t
            blk = int(tables[i, pos // bs])
            slot = pos % bs
            live_slots.add((blk, slot))
            np.testing.assert_array_equal(
                np.asarray(pk2[1, blk, :, slot, :]),
                np.asarray(rows_k[i, t]))
    # any other change is confined to the scratch block
    changed = np.any(np.asarray(pk2[1] != pk[1]), axis=(1, 3))  # (N, bs)
    for blk, slot in zip(*np.nonzero(changed)):
        assert blk == 0 or (int(blk), int(slot)) in live_slots, (blk, slot)
    np.testing.assert_array_equal(np.asarray(pk2[0]), np.asarray(pk[0]))
    # 4-D single-layer form
    pk1 = pa.scatter_kv_chunk(pk[1], tables, starts, rows_k, q_lens)
    np.testing.assert_array_equal(np.asarray(pk1), np.asarray(pk2[1]))


# -- int8 quantized pages (QuantPages) ----------------------------------------


def _quantize(pages):
    """Pool-layout quantization: per-(position x head) scale over head_dim."""
    return pa.QuantPages(*pa.quantize_kv_rows(pages))


@pytest.mark.parametrize("block_size", [4, 8])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (4, 1)],
                         ids=["mha", "gqa2", "mqa"])
def test_int8_kernel_matches_reference_ragged(block_size, heads):
    """Decode form on int8 pages: the in-kernel dequant agrees with the XLA
    reference's gather-dequant to f32 accumulation tolerance, and both stay
    within quantization error of the unquantized f32 attention."""
    h, hkv = heads
    q, pk, pv, tables, lens = _random_case(
        block_size * 1000 + h, block_size=block_size, num_heads=h,
        num_kv_heads=hkv)
    qpk, qpv = _quantize(pk), _quantize(pv)
    for layer in range(pk.shape[0]):
        ref = pa.paged_attention_reference(q, qpk, qpv, tables, lens,
                                           layer=layer)
        out = pa.paged_attention(q, qpk, qpv, tables, lens, layer=layer,
                                 backend="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        f32 = pa.paged_attention_reference(q, pk, pv, tables, lens,
                                           layer=layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(f32),
                                   atol=5e-2)


@pytest.mark.parametrize("qw", [4, 8])
def test_int8_multitoken_kernel_matches_reference(qw):
    """Ragged q chunks on int8 pages: chunk KV is quantized at write time by
    scatter_kv_chunk, then the kernel and reference agree; dead rows stay
    exactly 0."""
    q, pk, pv, tables, starts, q_lens, rows_k, rows_v = _random_chunk_case(
        4100 + qw, qw=qw)
    kv_lens = starts + q_lens
    qpk, qpv = _quantize(pk), _quantize(pv)
    qpk = pa.scatter_kv_chunk(qpk, tables, starts, rows_k, q_lens, layer=1)
    qpv = pa.scatter_kv_chunk(qpv, tables, starts, rows_v, q_lens, layer=1)
    assert isinstance(qpk, pa.QuantPages) and qpk.data.dtype == jnp.int8
    ref = pa.paged_attention_reference(q, qpk, qpv, tables, kv_lens,
                                       q_lens=q_lens, layer=1)
    out = pa.paged_attention(q, qpk, qpv, tables, kv_lens, q_lens=q_lens,
                             layer=1, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    assert np.all(np.asarray(out[0]) == 0)
    ql = np.asarray(q_lens)
    for i in range(q.shape[0]):
        assert np.all(np.asarray(out[i, ql[i]:]) == 0), i


def test_int8_scatter_rows_quantizes_at_write():
    """scatter_kv_rows on QuantPages stores int8 + per-row scale; the
    dequantized readback is within quantization error of the f32 rows, and
    untouched blocks keep both leaves bit-identical."""
    rng = np.random.default_rng(41)
    q, pk, pv, tables, lens = _random_case(43)
    qpk = _quantize(pk)
    b, h_kv, dh = q.shape[0], pk.shape[2], pk.shape[4]
    bs = pk.shape[3]
    rows = jnp.asarray(rng.normal(size=(b, h_kv, dh)), jnp.float32)
    offsets = lens - 1
    qpk2 = pa.scatter_kv_rows(qpk, tables, offsets, rows, layer=1)
    assert qpk2.data.dtype == jnp.int8 and qpk2.scale.dtype == jnp.float32
    for i in range(b):
        blk = int(tables[i, int(offsets[i]) // bs])
        slot = int(offsets[i]) % bs
        got = (np.asarray(qpk2.data[1, blk, :, slot, :], np.float32) *
               np.asarray(qpk2.scale[1, blk, :, slot, :]))
        np.testing.assert_allclose(got, np.asarray(rows[i]), atol=3e-2)
    # layer 0 untouched on BOTH leaves
    np.testing.assert_array_equal(np.asarray(qpk2.data[0]),
                                  np.asarray(qpk.data[0]))
    np.testing.assert_array_equal(np.asarray(qpk2.scale[0]),
                                  np.asarray(qpk.scale[0]))


def test_int8_mixed_kind_rejected():
    q, pk, pv, tables, lens = _random_case(47)
    with pytest.raises(ValueError, match="both"):
        pa.paged_attention(q, _quantize(pk), pv, tables, lens)


# -- the whole-page write against the per-row scatter it replaced -------------


def _oracle_rows(pages, tables, offsets, rows, layer=None):
    """The write as it was before the whole-page form: one scatter with
    indices on the layer, page and SLOT dims."""
    if isinstance(pages, pa.QuantPages):
        q, s = pa.quantize_kv_rows(rows)
        return pa.QuantPages(_oracle_rows(pages.data, tables, offsets, q, layer),
                             _oracle_rows(pages.scale, tables, offsets, s,
                                          layer))
    bs = pages.shape[-2]
    blk = jnp.take_along_axis(tables, (offsets // bs)[:, None], axis=1)[:, 0]
    blk, slot = jnp.maximum(blk, 0), offsets % bs
    if pages.ndim == 5:
        return pages.at[layer, blk, :, slot, :].set(rows)
    return pages.at[blk, :, slot, :].set(rows)


def _oracle_chunk(pages, tables, starts, rows, q_lens, layer=None):
    if isinstance(pages, pa.QuantPages):
        q, s = pa.quantize_kv_rows(rows)
        return pa.QuantPages(
            _oracle_chunk(pages.data, tables, starts, q, q_lens, layer),
            _oracle_chunk(pages.scale, tables, starts, s, q_lens, layer))
    bs, qw, nbt = pages.shape[-2], rows.shape[1], tables.shape[1]
    pos = starts[:, None] + jnp.arange(qw)
    live = jnp.arange(qw)[None, :] < q_lens[:, None]
    blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, nbt - 1), axis=1)
    blk, slot = jnp.maximum(jnp.where(live, blk, 0), 0), pos % bs
    if pages.ndim == 5:
        return pages.at[layer, blk, :, slot, :].set(rows)
    return pages.at[blk, :, slot, :].set(rows)


def _write_case(seed, *, qw, block_size=4, batch=5, blocks_per_row=6,
                holes=False, all_scratch=False, pack=1):
    """A pool, tables that keep the engine's one-writer invariant (every
    non-scratch page in one row's table only, scratch page 0 as padding) and
    a ragged chunk per row: row 0 absent (q_lens 0), row 1 a full chunk that
    starts on a page's last slot (the most pages a chunk can straddle), the
    last row padding whose table is all scratch."""
    del pack    # the test packs the pool after the oracle has written it
    rng = np.random.default_rng(seed)
    num_blocks = 1 + batch * blocks_per_row
    shape = (2, num_blocks, 2, block_size, 16)
    pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tables = rng.permutation(np.arange(1, num_blocks)).reshape(
        batch, blocks_per_row).astype(np.int32)
    room = blocks_per_row * block_size - qw
    starts = rng.integers(0, room + 1, size=batch).astype(np.int32)
    q_lens = rng.integers(1, qw + 1, size=batch).astype(np.int32)
    q_lens[0] = 0
    starts[1], q_lens[1] = block_size - 1, qw
    tables[-1] = 0
    if all_scratch:
        tables[:] = 0
    if holes:   # pages another SP shard owns: every other table entry
        tables[:, 1::2] = -1
    rows = jnp.asarray(rng.normal(size=(batch, qw, 2, 16)), jnp.float32)
    return (pages, jnp.asarray(tables), jnp.asarray(starts), rows,
            jnp.asarray(q_lens))


def _assert_same_but_scratch(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g), np.asarray(w)
        if g.ndim == 5:     # (L, N, ...): page 0 of every layer is scratch
            g, w = g[:, 1:], w[:, 1:]
        else:
            g, w = g[1:], w[1:]
        np.testing.assert_array_equal(g, w)


_WRITE_CASES = {
    # chunk widths that straddle at most 1, 2, 3, 4 and 5 pages of 4 slots
    "pages1": dict(qw=1), "pages2": dict(qw=3), "pages3": dict(qw=6),
    "pages4": dict(qw=10), "pages5": dict(qw=16),
    "holes": dict(qw=6, holes=True),
    "all_scratch": dict(qw=6, all_scratch=True),
    "one_layer": dict(qw=6), "traced_layer": dict(qw=6),
    "int8": dict(qw=6), "int8_holes": dict(qw=10, holes=True),
    # both heads of 16 in one page row of 32 (``pa.lane_pack``)
    "packed": dict(qw=6, pack=2), "packed_pages5": dict(qw=16, pack=2),
    "packed_holes": dict(qw=10, holes=True, pack=2),
    "packed_one_layer": dict(qw=6, pack=2),
}


@pytest.mark.parametrize("form", ["rows", "chunk"])
@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_page_write_matches_per_row_scatter(case, form):
    """scatter_kv_rows / scatter_kv_chunk (whole pages, in place) against the
    per-row scatter formula they replaced: bit-exact on every non-scratch
    page, whatever lands in the scratch page. A packed page takes the
    packed form of what the formula writes into the unpacked one."""
    pages, tables, starts, rows, q_lens = _write_case(
        sum(map(ord, case)), **_WRITE_CASES[case])
    pack = _WRITE_CASES[case].get("pack", 1)
    if case.startswith("int8"):
        pages = _quantize(pages)
    layer = 1
    if case.endswith("one_layer"):
        pages, layer = pages[1], None
    if form == "rows":
        # the decode form: every row writes one position (a padding row's
        # table is all scratch)
        args, new, old = (starts, rows[:, 0]), pa.scatter_kv_rows, _oracle_rows
    else:
        args, new, old = (starts, rows, q_lens), pa.scatter_kv_chunk, \
            _oracle_chunk
    want = old(pages, tables, *args, layer=layer)
    if pack > 1:
        pages, want = _pack(pages, pack), _pack(want, pack)
    if case == "traced_layer":
        got = jax.jit(lambda p, ly: new(p, tables, *args, layer=ly))(
            pages, jnp.asarray(layer, jnp.int32))
    else:
        got = new(pages, tables, *args, layer=layer)
    _assert_same_but_scratch(got, want)
    if case == "all_scratch":   # and nothing but the scratch page changed
        _assert_same_but_scratch(got, pages)


# -- the row write: a row's sublane tile through one aliased pallas_call ------
#
# ``tnn_kv_row_write`` (what ``write_rows`` is on the chip: ``_kernel_writes``,
# here answered for it and the kernel interpreted) against the whole-page form,
# which the cases above hold to the per-row scatter: the same bits on every
# non-scratch page.


def _write_as(kernel, *args, write=pa.write_rows, **kw):
    """``write`` with the form chosen for it: ``tnn_kv_row_write`` for every
    array (``kernel``), or whole pages."""
    with mock.patch.object(pa, "_kernel_writes", lambda pages: kernel):
        return write(*args, **kw)


def _row_tile_case(seed, *, qw, dtype=jnp.bfloat16, bs=32, heads=2, width=128,
                   pack=1, batch=4, holes=False, past_table=False,
                   short=None):
    """A pool of pages of ``bs`` rows (two tiles of 16 bf16 rows, four of 8
    float32), one-writer tables, and a ragged chunk a row: row 0 absent
    (``q_lens`` 0), row 1 a full chunk from a page's last row (a start in
    mid-tile, and the most tiles and pages a chunk can cross), row 2 a short
    one (``short`` positions, a third of the chunk unless given) from
    mid-tile, the last a full chunk from a random place. ``holes`` stamps -1
    over every other table entry; ``past_table`` starts row 1 so that its
    chunk runs past the table's last entry."""
    rng = np.random.default_rng(seed)
    per_row = -(-(qw + bs) // bs) + 1
    num_blocks = 1 + batch * per_row
    shape = (2, num_blocks, heads // pack, bs, pack * width)
    pages = jnp.asarray(rng.normal(size=shape), dtype)
    tables = rng.permutation(np.arange(1, num_blocks)).reshape(
        batch, per_row).astype(np.int32)
    starts = rng.integers(0, per_row * bs - qw + 1, size=batch).astype(
        np.int32)
    q_lens = np.full(batch, qw, np.int32)
    q_lens[0] = 0
    starts[1] = per_row * bs - qw // 2 - 1 if past_table else bs - 1
    starts[2], q_lens[2] = bs + 5, short or max(1, qw // 3)
    if holes:
        tables[:, 1::2] = -1
    rows = jnp.asarray(rng.normal(size=(batch, qw, heads, width)), dtype)
    return (pages, jnp.asarray(tables), jnp.asarray(starts), rows,
            jnp.asarray(q_lens))


_ROW_TILE_CASES = {
    "decode": dict(qw=1), "chunk16": dict(qw=16), "chunk64": dict(qw=64),
    "chunk256": dict(qw=256), "chunk5": dict(qw=5),
    # a ragged chunk of 0, 1 and Q live positions
    "ragged_0_1_q": dict(qw=64, short=1),
    "f32_decode": dict(qw=1, dtype=jnp.float32),
    "f32_chunk16": dict(qw=16, dtype=jnp.float32),
    # two heads of 64 in one page row of 128 (``pa.lane_pack``)
    "packed_decode": dict(qw=1, width=64, pack=2),
    "packed_chunk64": dict(qw=64, width=64, pack=2),
    "packed_bs16_decode": dict(qw=1, width=64, pack=2, bs=16, heads=4),
    "packed_bs16_chunk64": dict(qw=64, width=64, pack=2, bs=16, heads=4),
    "packed_bs128_decode": dict(qw=1, width=64, pack=2, bs=128),
    "packed_bs128_chunk64": dict(qw=64, width=64, pack=2, bs=128),
    "one_layer_decode": dict(qw=1), "one_layer_chunk16": dict(qw=16),
    "traced_layer": dict(qw=16),
    # the latent pool's ONE head of [c_kv | k_rope | 0] rows
    "latent384_decode": dict(qw=1, heads=1, width=384),
    "latent384_chunk64": dict(qw=64, heads=1, width=384),
    "latent640_decode": dict(qw=1, heads=1, width=640),
    "latent640_chunk16": dict(qw=16, heads=1, width=640),
    "holes_decode": dict(qw=1, holes=True),
    "holes_chunk64": dict(qw=64, holes=True),
    "past_table": dict(qw=64, past_table=True),
    # an int8 pool: the data at tiles of 32 rows, the float32 scale sidecar
    # (one lane) at tiles of 8
    "int8_decode": dict(qw=1, dtype=jnp.float32, bs=64),
    "int8_chunk64": dict(qw=64, dtype=jnp.float32, bs=64),
    "int8_holes": dict(qw=16, dtype=jnp.float32, bs=64, holes=True),
    "int8_one_layer": dict(qw=16, dtype=jnp.float32, bs=64),
    # a page that IS one tile (GPT-2's 16 rows), and a page of no whole
    # tiles, which moves whole
    "page_is_tile": dict(qw=16, bs=16), "page_is_tile_decode": dict(qw=1,
                                                                    bs=16),
    "small_page": dict(qw=5, bs=4),
    # a decode batch whose new rows are too many to sit whole in VMEM (a
    # row's ONE sublane pads to 16): they come by DMA, a row beside its tile
    "wide_batch_decode": dict(qw=1, bs=16, batch=400),
}


@pytest.mark.parametrize("case", list(_ROW_TILE_CASES))
def test_row_write_kernel_matches_page_form(case):
    """Bit for bit on every non-scratch page, and a write that changed what
    it should: every live row of every writing table entry, nothing else."""
    pages, tables, starts, rows, q_lens = _row_tile_case(
        sum(map(ord, case)), **_ROW_TILE_CASES[case])
    if case.startswith("int8"):
        pages = _quantize(pages)
    layer = 1
    if "one_layer" in case:
        pages, layer = jax.tree_util.tree_map(lambda x: x[1], pages), None

    def write(kernel, layer=layer):
        return _write_as(kernel, pages, tables, starts, rows, q_lens,
                         layer=layer)

    want = write(False)
    if case == "traced_layer":
        got = jax.jit(lambda ly: write(True, ly))(
            jnp.asarray(layer, jnp.int32))
    else:
        got = write(True)
    assert type(got) is type(pages)
    _assert_same_but_scratch(got, want)
    if case.startswith("int8"):     # the page form is held to the formula
        return                      # above; here the two halves agree
    # the page form itself: the live rows hold the new values
    bs, nbt = pages.shape[-2], tables.shape[1]
    flat = np.asarray(rows.reshape(rows.shape[:2] + pages.shape[-3:-2]
                                   + pages.shape[-1:]), np.float32)
    out = np.asarray(got if layer is None else got[layer], np.float32)
    written = 0
    for i in range(rows.shape[0]):
        for tkn in range(int(q_lens[i])):
            pos = int(starts[i]) + tkn
            if pos // bs < nbt and int(tables[i, pos // bs]) > 0:
                np.testing.assert_array_equal(
                    out[int(tables[i, pos // bs]), :, pos % bs], flat[i, tkn])
                written += 1
    assert written > 0


@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_row_write_kernel_window_relative_starts(form):
    """The windowed model's exact rows: positions relative to the window's
    start, through the exact slice of a table that carries the summary
    pages behind it (``tables[:, :n_exact]``). A chunk that reaches the
    window's end stops at the slice's last entry, and no summary page
    changes."""
    bs, n_exact, n_sum, batch, qw = 32, 3, 2, 4, 1 if form == "decode" else 48
    rng = np.random.default_rng(7)
    num_blocks = 1 + batch * (n_exact + n_sum)
    pages = jnp.asarray(rng.normal(size=(2, num_blocks, 2, bs, 128)),
                        jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, num_blocks)).reshape(
        batch, n_exact + n_sum).astype(np.int32))
    window = n_exact * bs
    # absolute positions in the second and third window, one row up to the
    # window's last position
    at = np.asarray([window + 5, 2 * window + bs - 3, window * 2 - qw,
                     window + 2 * bs - 1], np.int32)
    starts = jnp.asarray(at % window)
    q_lens = jnp.asarray([qw, qw, qw, min(qw, bs + 1)], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(batch, qw, 2, 128)), jnp.bfloat16)
    args = (tables[:, :n_exact], starts, rows, q_lens)
    want = _write_as(False, pages, *args, layer=0)
    got = _write_as(True, pages, *args, layer=0)
    _assert_same_but_scratch(got, want)
    summary = np.asarray(tables[:, n_exact:]).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(got[:, summary], np.float32),
        np.asarray(pages[:, summary], np.float32))
    assert np.any(np.asarray(got[0] != pages[0]))


@pytest.mark.parametrize("qw", [1, 16, 64])
def test_row_write_kernel_summary_rows(qw):
    """``eva_attention.write_summaries`` whole, in both forms: the rows are a
    step's COMPLETED chunks (``q_lens`` = chunks completed: 0 for most rows
    of a decode step), float32 results cast to the pages' dtype, at row
    ``c`` of the summary pages (``tables[:, n_exact:]``), K and V."""
    from tnn_tpu.ops.pallas import eva_attention as eva

    bs, n_exact, n_sum, batch, window, chunk = 32, 2, 1, 4, 64, 16
    rng = np.random.default_rng(qw)
    num_blocks = 1 + batch * (n_exact + n_sum)
    shape = (2, num_blocks, 2, bs, 128)
    pk, pv = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
              for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, num_blocks)).reshape(
        batch, n_exact + n_sum).astype(np.int32))
    # row 0 completes nothing, row 1 ends on a chunk's last position, row 2
    # is absent, row 3 completes every chunk its width can
    starts = jnp.asarray([3, chunk - min(qw, chunk), 40,
                          window + chunk - min(qw, chunk)], jnp.int32)
    q_lens = jnp.asarray([min(qw, 5), min(qw, chunk), 0, qw], jnp.int32)
    phi, mu = (jnp.asarray(rng.normal(size=(2, 128)), jnp.float32)
               for _ in range(2))
    kw = dict(n_exact=n_exact, window=window, chunk=chunk, layer=1, qw=qw)
    args = (pk, pv, tables, starts, q_lens, phi, mu)
    want = _write_as(False, *args, write=eva.write_summaries, **kw)
    got = _write_as(True, *args, write=eva.write_summaries, **kw)
    for g, w, old in zip(got, want, (pk, pv)):
        _assert_same_but_scratch(g, w)
        assert np.any(np.asarray(g[1] != old[1]))       # something completed
        exact = np.asarray(tables[:, :n_exact]).reshape(-1)
        np.testing.assert_array_equal(np.asarray(g[:, exact], np.float32),
                                      np.asarray(old[:, exact], np.float32))


@pytest.mark.parametrize("slots", [2, 3, 5])
@pytest.mark.parametrize("qw", [1, 64])
def test_row_write_kernel_ring_shorter_than_the_live_tiles(qw, slots,
                                                           monkeypatch):
    """More live tiles than the ring has slots (a wide page's ring at real
    sizes: 24 slots for a prompt step's 136 tiles): a slot takes the tile a
    ring further on once its own is stored, and the last ones are waited
    for after the walk."""
    monkeypatch.setattr(pa, "_WRITE_SLOTS", slots)
    pages, tables, starts, rows, q_lens = _row_tile_case(qw + slots, qw=qw,
                                                         batch=7)
    q_lens = q_lens.at[3].set(0)        # a dead row between live ones
    args = (pages, tables, starts, rows, q_lens)
    want = _write_as(False, *args, layer=0)
    got = _write_as(True, *args, layer=0)
    _assert_same_but_scratch(got, want)
    assert np.any(np.asarray(got[0] != pages[0]))


def test_row_write_kernel_scratch_only_and_other_layers_untouched():
    """Tables of scratch entries and holes alone: nothing but page 0 may
    change; and the layers a write does not name keep every bit."""
    pages, tables, starts, rows, q_lens = _row_tile_case(5, qw=64)
    dead = jnp.where(jnp.arange(tables.shape[1]) % 2 == 0, 0, -1) \
        * jnp.ones_like(tables)
    got = _write_as(True, pages, dead, starts, rows, q_lens, layer=1)
    _assert_same_but_scratch(got, pages)
    got = _write_as(True, pages, tables, starts, rows, q_lens, layer=1)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(pages[0], np.float32))
    assert np.any(np.asarray(got[1] != pages[1]))


def test_row_write_form_follows_platform_and_shape():
    """The choice is made from what the code can see, an array at a time:
    the kernel on the TPU for pages of whole registers, under the
    ``kv_write`` scope's one level; the whole-page form off the chip, for
    rows that do not fill the 128 lanes (an int8 pool's scale sidecar; heads
    of 64 that cannot pack) and for a ``bs`` of no whole tiles. No keyword
    chooses."""
    import inspect

    pages, tables, starts, rows, q_lens = _row_tile_case(9, qw=16,
                                                         dtype=jnp.float32)
    quant = _quantize(pages)

    def write(p):
        return pa.scatter_kv_chunk(p, tables, starts, rows, q_lens, layer=1)

    def traced(p):      # a new function a call: no trace is found again
        return str(jax.make_jaxpr(lambda x: write(x))(p))

    def kernels(p):
        return traced(p).count("tnn_kv_row_write")

    want = write(quant)
    assert kernels(pages) == kernels(quant) == 0        # off the chip
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        plain = traced(pages)
        assert plain.count("pallas_call") == 1 and kernels(pages) == 1
        assert "scatter" not in plain
        # bs 32: the int8 data is one tile a page, the scale keeps the pages
        assert kernels(quant) == 1 and "scatter" in traced(quant)
        got = write(quant)
        for shape, dtype, kernel in (
                ((2, 9, 3, 16, 64), jnp.bfloat16, False),   # half the lanes
                ((2, 9, 2, 12, 128), jnp.float32, False),   # 1.5 tiles
                ((2, 9, 2, 24, 128), jnp.bfloat16, False),
                ((2, 9, 2, 16, 128), jnp.int8, False),      # half a tile
                ((9, 1, 128, 640), jnp.bfloat16, True)):
            assert pa._kernel_writes(
                jax.ShapeDtypeStruct(shape, dtype)) is kernel, shape
    _assert_same_but_scratch(got, want)
    for fn in (pa.write_rows, pa.scatter_kv_chunk, pa.scatter_kv_rows):
        assert set(inspect.signature(fn).parameters) <= {
            "pages", "block_tables", "starts", "offsets", "rows", "q_lens",
            "layer"}


# the six serving configurations' pages (chipbench/configs/*-serve.json):
# KV heads of a pool row, head width, page rows; rows a step, chunk width
_CELL_PAGES = {
    "gpt2-large": (20, 64, 16, 64), "evabyte-pp2": (32, 128, 128, 256),
    "trinity-large-ep8": (8, 128, 128, 64),
    "qwen3-next-ep4": (2, 256, 128, 32),
    "mistral-small4-ep4": (1, 384, 128, 64),
    "longcat-flash-ep32": (1, 640, 128, 32),
}


def _row_write_call(batch, qw, hkv, dh, bs):
    """The kernel as ``write_rows`` traces it at a pool's shapes (nothing is
    allocated): the ``pallas_call``'s VMEM scratch shapes and the memory
    space of its new-rows operand."""
    p = pa.lane_pack(hkv, dh, jnp.bfloat16)
    shapes = [((2, 3, hkv // p, bs, p * dh), jnp.bfloat16),
              ((batch, 4), jnp.int32), ((batch,), jnp.int32),
              ((batch, qw, hkv, dh), jnp.bfloat16), ((batch,), jnp.int32)]

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    call, = calls(jax.make_jaxpr(functools.partial(
        _write_as, True, layer=1))(
            *(jax.ShapeDtypeStruct(*s) for s in shapes)).jaxpr)
    assert call.params["input_output_aliases"] == ((3, 0),)
    scratch = [a for a in call.params["grid_mapping"].scratch_avals
               if "vmem" in str(a.memory_space).lower()]
    new_rows = call.params["jaxpr"].invars[2].aval
    return [a.shape for a in scratch], str(new_rows.memory_space).lower()


@pytest.mark.parametrize("cell", list(_CELL_PAGES))
def test_row_write_moves_tiles_not_pages(cell):
    """What the KERNEL moves, read from its traced call: a ring slot is one
    tile of 16 bf16 rows of the page, ``16 / bs`` of the page the other form
    gathers and scatters (the same bytes at GPT-2's pages of 16, which are
    one tile), a decode row walks ONE and a chunk ``row_tiles``, no more
    than the pages it can touch."""
    hkv, dh, bs, chunk = _CELL_PAGES[cell]
    p = pa.lane_pack(hkv, dh, jnp.bfloat16)
    page = 2 * hkv * dh * bs * 2        # a page of bf16 in and out
    assert pa.write_tile(bs, jnp.bfloat16) == 16
    for qw in (1, chunk, 256):
        (slots, *slot), *new_ring = _row_write_call(8, qw, hkv, dh, bs)[0]
        assert tuple(slot) == (hkv // p, 16, p * dh) and 2 <= slots <= 32
        # a chunk's new rows come through a ring like the tiles'; a decode
        # step's few are whole in VMEM
        assert new_ring == ([] if qw == 1 else [(slots, *slot)])
        moved = 2 * pa.row_tiles(qw, 16) * int(np.prod(slot)) * 2
        assert moved == pa.row_tiles(qw, 16) * page * 16 // bs
        assert moved <= pa.row_tiles(qw, bs) * page or bs == 16
        if qw == 1:
            assert (moved == page) == (cell == "gpt2-large")
    # a decode row of a 64-wide mixed step: one tile, where the page form
    # moved every page the chunk's width could touch
    assert pa.row_tiles(1, 16) == 1 and pa.row_tiles(64, 16) == 5
    assert pa.row_tiles(256, 16) == 17


@pytest.mark.parametrize("cell,batch", [("evabyte-pp2", 128),
                                        ("gpt2-large", 512)])
def test_row_write_vmem_is_bounded_in_the_batch(cell, batch):
    """A decode step's new rows pad ONE sublane to a tile's 16: whole in
    VMEM they would take 16 and 20 MiB here. Past half the write's budget
    they stay in HBM, laid out by tile, and come by DMA into a ring beside
    their tiles', so the kernel's VMEM does not grow with
    ``--max-batch-size``."""
    hkv, dh, bs, _ = _CELL_PAGES[cell]
    p = pa.lane_pack(hkv, dh, jnp.bfloat16)
    for b, where in ((8, "vmem"), (batch, "any")):
        scratch, space = _row_write_call(b, 1, hkv, dh, bs)
        assert where in space
        assert len(scratch) == (1 if where == "vmem" else 2)
        held = sum(s[0] * s[1] * pa._tile_bytes(s[2], s[3], jnp.bfloat16)
                   for s in scratch)
        if where == "vmem":
            held += b * hkv // p * pa._tile_bytes(1, p * dh, jnp.bfloat16)
        assert held <= pa._WRITE_VMEM


# -- the grouped grid step: several pages and every head a step ---------------
#
# A grid step fetches ``pages`` consecutive table entries of ``heads`` heads
# (``pa.fetch_group``) and makes ONE softmax update over the group. The cases
# above run it too (at their sizes one group holds a whole table); these put
# group boundaries, padding, holes and dead rows where the grouping can go
# wrong: pages of 16, so a group is 8 pages = 128 positions.

_GROUP_BS = 16


def _group_case(seed, kv_lens, *, heads=(4, 4), head_dim=64, qw=None,
                q_lens=None, nb=20, num_layers=2, dtype=jnp.float32,
                holes=()):
    """Pool + tables for rows of the given lengths (table entries past a
    row's live pages point at scratch page 0). ``qw`` None is the decode
    form; ``holes`` are (row, entry) table slots stamped -1."""
    rng = np.random.default_rng(seed)
    h, hkv = heads
    b = len(kv_lens)
    live = [-(-int(n) // _GROUP_BS) for n in kv_lens]
    num_blocks = sum(live) + 1
    shape = (num_layers, num_blocks, hkv, _GROUP_BS, head_dim)
    pk = jnp.asarray(rng.normal(size=shape), dtype)
    pv = jnp.asarray(rng.normal(size=shape), dtype)
    perm = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((b, nb), np.int32)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = perm[at:at + n]
        at += n
    for row, entry in holes:
        tables[row, entry] = -1
    qshape = (b, h, head_dim) if qw is None else (b, qw, h, head_dim)
    q = jnp.asarray(rng.normal(size=qshape), dtype)
    if q_lens is not None:
        q_lens = jnp.asarray(q_lens, jnp.int32)
    return (q, pk, pv, jnp.asarray(tables),
            jnp.asarray(kv_lens, jnp.int32), q_lens)


def _assert_matches_reference(case, *, layer=1, atol=2e-5, stats=False,
                              pack=1):
    """The kernel (interpret mode) against the XLA path. With ``pack`` both
    read the pool packed, and are held to the XLA path over the UNPACKED
    pool (a query head's zero lanes add exact zeros: same tolerance)."""
    q, pk, pv, tables, kv_lens, q_lens = case
    kw = dict(q_lens=q_lens, layer=layer, return_stats=stats)
    ref = pa.paged_attention(q, pk, pv, tables, kv_lens, backend="xla", **kw)
    if pack > 1:
        pk, pv = _pack(pk, pack), _pack(pv, pack)
    outs = [pa.paged_attention(q, pk, pv, tables, kv_lens, backend=backend,
                               **kw)
            for backend in (("pallas", "xla") if pack > 1 else ("pallas",))]
    for out in outs:
        for got, want in zip(out if stats else (out,),
                             ref if stats else (ref,)):
            assert got.shape == want.shape
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       atol=atol, rtol=atol)
    return outs[0][0] if stats else outs[0]


# lengths that end inside a group, at its end and one past it, a whole table
# (20 entries: no multiple of the group's 8, so the table is padded), one
# position, and a row of length 0
_GROUP_LENS = [0, 1, 100, 128, 129, 256, 257, 320]


# (query heads, KV heads), head dim, heads a page row
_PACKED = {"g1-p2": ((4, 4), 64, 2), "g2-p2": ((4, 2), 64, 2),
           "g4-p2": ((8, 2), 64, 2), "g1-p4": ((4, 4), 32, 4),
           "g2-p4": ((8, 4), 32, 4)}
_DECODE_SHAPES = {f"{g}-dh{dh}": (heads, dh, 1)
                  for dh in (64, 128)
                  for g, heads in (("g1", (4, 4)), ("g2", (4, 2)),
                                   ("g4", (4, 1)))}
_DECODE_SHAPES.update(_PACKED)


@pytest.mark.parametrize("shape", list(_DECODE_SHAPES))
def test_grouped_decode_lengths_around_group_ends(shape):
    heads, head_dim, pack = _DECODE_SHAPES[shape]
    case = _group_case(head_dim + heads[1], _GROUP_LENS, heads=heads,
                       head_dim=head_dim)
    # to the kernel a packed row is one KV head of ``pack * head_dim`` with
    # ``pack`` query groups: every such head of 8 pages a grid step
    assert pa.fetch_group(bs=_GROUP_BS, dh=pack * head_dim,
                          hkv=heads[1] // pack,
                          qg=pack * heads[0] // heads[1],
                          page_dtype=jnp.float32,
                          nb=20) == (8, heads[1] // pack)
    out = _assert_matches_reference(case, pack=pack)
    assert np.all(np.asarray(out[0]) == 0)      # the row of length 0


_CHUNK_SHAPES = {"g1": ((4, 4), 64, 1), "g4": ((4, 1), 64, 1), **_PACKED}


@pytest.mark.parametrize("shape", list(_CHUNK_SHAPES))
@pytest.mark.parametrize("qw", [1, 8, 64])
def test_grouped_chunks_with_dead_rows(qw, shape):
    """Ragged chunks across group ends: a row with no query token (its
    context still live) and a row of length 0 beside live rows, a decode
    row, chunks that start before a group's end and end after it."""
    heads, head_dim, pack = _CHUNK_SHAPES[shape]
    kv_lens = [200, 0, 129, 128 + qw // 2, 320, 77]
    q_lens = [0, 0, 1, min(qw, 128 + qw // 2), qw, min(qw, 77)]
    case = _group_case(qw * 10 + heads[1], kv_lens, heads=heads, qw=qw,
                       q_lens=q_lens, head_dim=head_dim)
    out = np.asarray(_assert_matches_reference(case, pack=pack))
    for i, n in enumerate(q_lens):
        assert np.all(out[i, n:] == 0), i


@pytest.mark.parametrize("pack", [1, 2], ids=["flat", "p2"])
@pytest.mark.parametrize("form", ["decode", "chunk8"])
def test_grouped_holes_inside_a_live_group_with_stats(form, pack):
    """-1 table entries (pages another sequence-parallel shard owns) in the
    middle of live groups, a group that holds nothing but holes, and a row
    whose every page is a hole: out, m and l all match the reference's
    (m and l are a query head's, whatever row its KV head lies in)."""
    holes = [(0, 1), (0, 2), (0, 9), (1, 0), (2, 3)] \
        + [(3, e) for e in range(8, 16)] + [(4, e) for e in range(5)]
    qw = None if form == "decode" else 8
    q_lens = None if qw is None else [8, 1, 5, 8, 8, 0]
    case = _group_case(71, [300, 129, 64, 320, 80, 40], qw=qw, q_lens=q_lens,
                       heads=(4, 2), holes=holes)
    _assert_matches_reference(case, stats=True, pack=pack)


@pytest.mark.parametrize("form", ["decode", "chunk8"])
def test_grouped_int8_pages(form):
    qw = None if form == "decode" else 8
    q_lens = None if qw is None else [8, 1, 0, 4, 8]
    q, pk, pv, tables, kv_lens, q_lens = _group_case(
        83, [300, 129, 50, 128, 16], qw=qw, q_lens=q_lens, heads=(4, 2))
    case = (q, _quantize(pk), _quantize(pv), tables, kv_lens, q_lens)
    _assert_matches_reference(case)


@pytest.mark.parametrize("pages", ["traced-layer", "one-layer", "bf16"])
def test_grouped_layer_forms(pages):
    """A traced ``layer`` (the scalar-prefetch operand), one layer's 4-D
    pages, and bf16 pages at the serving cell's page shape."""
    dtype = jnp.bfloat16 if pages == "bf16" else jnp.float32
    q, pk, pv, tables, kv_lens, _ = _group_case(
        97, [300, 129, 7], heads=(4, 2), dtype=dtype)
    if pages == "one-layer":
        out = pa.paged_attention(q, pk[1], pv[1], tables, kv_lens,
                                 backend="pallas")
    else:
        run = jax.jit(lambda layer: pa.paged_attention(
            q, pk, pv, tables, kv_lens, layer=layer, backend="pallas"))
        out = run(jnp.asarray(1, jnp.int32))
    ref = pa.paged_attention_reference(q, pk, pv, tables, kv_lens, layer=1)
    atol = 3e-2 if pages == "bf16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


# (bs, dh, hkv, qg, page dtype, nb) -> the (pages, heads) it must give
_FETCH_GROUPS = {
    "gpt2-large-decode": ((16, 64, 20, 1, jnp.bfloat16, 64), (8, 20)),
    "gpt2-large-chunk64": ((16, 64, 20, 64, jnp.bfloat16, 64), (8, 20)),
    "gpt2-large-tp4-shard": ((16, 64, 5, 64, jnp.bfloat16, 64), (8, 5)),
    "gpt2-large-int8": ((16, 64, 20, 64, jnp.int8, 64), None),
    "llama-dh128-g4": ((16, 128, 8, 256, jnp.bfloat16, 128), None),
    "evabyte-width-heads": ((16, 128, 32, 64, jnp.bfloat16, 128), None),
    "pages-of-128": ((128, 128, 32, 256, jnp.bfloat16, 32), None),
    "short-table": ((16, 64, 20, 1, jnp.bfloat16, 3), (3, 20)),
}


@pytest.mark.parametrize("shape", list(_FETCH_GROUPS))
def test_fetch_group_stays_inside_its_vmem_budget(shape):
    (bs, dh, hkv, qg, dtype, nb), want = _FETCH_GROUPS[shape]
    pages, heads = pa.fetch_group(bs=bs, dh=dh, hkv=hkv, qg=qg,
                                  page_dtype=dtype, nb=nb)
    assert 1 <= pages <= nb and hkv % heads == 0
    assert pages * bs <= max(bs, 128)
    assert pa.group_vmem_bytes(pages, heads, bs=bs, dh=dh, qg=qg,
                               page_dtype=dtype) <= pa._VMEM_BUDGET
    if heads < hkv:     # the next divisor up would not have fitted
        up = min(h for h in range(heads + 1, hkv + 1) if hkv % h == 0)
        assert pa.group_vmem_bytes(pages, up, bs=bs, dh=dh, qg=qg,
                                   page_dtype=dtype) > pa._VMEM_BUDGET
    if want is not None:
        assert (pages, heads) == want


# (head dim, KV heads one device holds, page dtype) -> heads a page row
_LANE_PACKS = {
    "gpt2-large": ((64, 20, jnp.bfloat16), 2),
    "dh128": ((128, 32, jnp.bfloat16), 1),
    "gpt2-large-tp4-shard": ((64, 5, jnp.bfloat16), 1),
    "one-kv-head": ((64, 1, jnp.bfloat16), 1),
    "dh32": ((32, 8, jnp.bfloat16), 4),
    "dh32-6-heads": ((32, 6, jnp.float32), 3),
    "tiny-test-model": ((16, 2, jnp.float32), 2),
    "dh256": ((256, 8, jnp.bfloat16), 1),
    "int8": ((64, 20, jnp.int8), 1),
}


@pytest.mark.parametrize("shape", list(_LANE_PACKS))
def test_lane_pack_rule(shape):
    """The largest divisor of the device's KV heads at most ``128 // Dh``;
    1 for int8 pages. ``PagedKVPool`` builds what it says."""
    from tnn_tpu.serving.kv_pool import PagedKVPool

    (dh, hkv, dtype), want = _LANE_PACKS[shape]
    assert pa.lane_pack(hkv, dh, dtype) == want
    int8 = jnp.dtype(dtype) == jnp.int8
    pool = PagedKVPool(2, hkv, dh, 3, 4,
                       dtype=jnp.float32 if int8 else dtype,
                       kv_dtype="int8" if int8 else "f32")
    assert pool.lane_pack == want
    assert pool.page_shape == (2, 3, hkv // want, 4, want * dh)
    data = pool.pages_k.data if int8 else pool.pages_k
    assert data.shape == pool.page_shape
    assert pool.kv_bytes_per_token == 2 * 2 * hkv * dh * data.dtype.itemsize


# heads, head dim -> heads a page row at tp = 1 and at tp = 2
_TP_POOLS = {"whole-groups": (4, 8, 4, 2), "odd-shard": (6, 64, 2, 1)}


@pytest.mark.parametrize("shape", list(_TP_POOLS))
def test_tp_shard_owns_whole_page_rows(shape):
    """``tp`` = 2 on the CPU mesh. Four heads of 8: a shard holds 2, the pool
    packs them into one row and each device holds whole rows. Six heads of
    64: a shard's 3 heads are odd, so its pool stays unpacked (today's cost,
    not an error) where ``tp`` = 1 packs pairs. Both token-exact against
    ``tp`` = 1 and the offline reference."""
    from tnn_tpu.models.gpt2 import GPT2, generate
    from tnn_tpu.serving import InferenceEngine

    heads, dh, pack1, pack2 = _TP_POOLS[shape]
    model = GPT2(vocab_size=128, max_len=64, num_layers=2,
                 d_model=dh * heads, num_heads=heads)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(heads)
    prompts = [rng.integers(0, 128, int(n)).astype(np.int32)
               for n in (5, 11, 7, 13)]

    def run(tp):
        eng = InferenceEngine(model, params, num_blocks=32, block_size=4,
                              max_batch_size=4, max_seq_len=32, tp=tp)
        rids = [eng.submit(p, 8) for p in prompts]
        out = eng.run_until_complete()
        eng.check_invariants()
        return eng, [out[r] for r in rids]

    one, want = run(1)
    two, got = run(2)
    assert one.pool.lane_pack == pack1
    assert two.pool.lane_pack == two.stats()["kv_lane_pack"] == pack2
    assert two.pool.page_shape == (2, 32, heads // pack2, 4, pack2 * dh)
    assert two.pool.pages_k.sharding.shard_shape(two.pool.page_shape) \
        == (2, 32, heads // pack2 // 2, 4, pack2 * dh)
    assert got == want
    for p, toks in zip(prompts, got):
        ref = np.asarray(generate(model, params, p[None], 8, max_len=32))[0]
        assert ref.tolist() == toks


def test_attn_fetch_fill_mean_is_a_hand_count():
    """``summary()["attn_fetch_fill_mean"]``: a row's live pages over the
    page slots of the groups the kernel fetches for them, mean over rows
    and steps, with the group the kernel's own launch derives."""
    from tnn_tpu.models.gpt2 import GPT2
    from tnn_tpu.serving import InferenceEngine

    model = GPT2(vocab_size=128, max_len=512, num_layers=1, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    eng = InferenceEngine(model, params, num_blocks=40, block_size=16,
                          max_batch_size=4, max_seq_len=512)
    assert "attn_fetch_fill_mean" not in eng.metrics.summary()
    # the pool holds both heads of 16 in ONE row of 32: to the kernel one KV
    # head of 32 with a query group of 2
    assert eng.pool.page_shape == (1, 40, 1, 16, 32)
    assert eng._attn_group(1) == pa.fetch_group(
        bs=16, dh=32, hkv=1, qg=2, page_dtype=eng.pool.dtype, nb=32) == (8, 1)
    # rows of 1, 128, 129 and 300 positions hold 1, 8, 9 and 19 pages, in
    # 1, 1, 2 and 3 groups of 8 slots; the fifth entry is not a live row
    eng._observe_attention([None] * 4, np.array([1, 128, 129, 300, 77]), 1)
    want = (1 / 8 + 8 / 8 + 9 / 16 + 19 / 24) / 4
    assert eng.metrics.summary()["attn_fetch_fill_mean"] \
        == pytest.approx(want)
    # a step whose rows hold nothing yet adds nothing
    eng._observe_attention([None] * 2, np.array([0, 0]), 64)
    assert eng.metrics.summary()["attn_fetch_fill_mean"] \
        == pytest.approx(want)
    # and the engine feeds it: a 20-token prompt decoding 3 tokens never
    # holds more than 2 of a group's 8 slots
    eng.submit(np.arange(20, dtype=np.int32), 3)
    eng.run_until_complete()
    assert eng.metrics.attn_fetch_row_steps >= 4 + 3
    assert eng.metrics.summary()["attn_fetch_fill_mean"] < want


# -- query tiles: a short row in a wide launch computes its first tile alone ---
#
# A row's ``Q * g`` query rows are cut into tiles of one register's sublanes
# (``pa.query_tile``); a grid step of a row whose live query rows fit the
# first tile computes that tile alone, so a decode row in a wide mixed step
# pays for one. One launch whose rows hold nothing, one token (twice), less
# than a tile, a tile and a bit, two tiles and a bit, and the full width;
# contexts that end inside, at and past a group of 128 positions.

_TILE_Q_LENS = [0, 1, 1, 7, 9, 17, 64]
_TILE_KV_LENS = [200, 129, 1, 77, 132, 320, 300]
# GPT-2 large's packed page row: 20 heads of 64, two a row -> to the kernel
# 10 page rows with a query group of 2. Trinity's: 6 query heads a KV head
_GPT2_LARGE = dict(heads=(20, 20), head_dim=64, pack=2)
_TILE_FORMS = {
    "gpt2-large": dict(_GPT2_LARGE),
    "gpt2-large-bf16": dict(_GPT2_LARGE, dtype=jnp.bfloat16, atol=3e-2),
    "stats": dict(_GPT2_LARGE, stats=True),
    "holes": dict(_GPT2_LARGE, stats=True,
                  holes=[(0, 1), (5, 3), (5, 9), (6, 0), (6, 18)]),
    "int8": dict(heads=(20, 20), head_dim=64, pack=1, quant=True),
    "trinity-g6": dict(heads=(12, 2), head_dim=128, pack=1),
}


def _tile_case(qw, *, heads, head_dim, dtype=jnp.float32, holes=()):
    q_lens = [min(n, qw) for n in _TILE_Q_LENS]
    return _group_case(qw + heads[0], _TILE_KV_LENS, heads=heads,
                       head_dim=head_dim, qw=qw, q_lens=q_lens, dtype=dtype,
                       holes=holes), q_lens


@pytest.mark.parametrize("form", list(_TILE_FORMS))
@pytest.mark.parametrize("qw", [64, 32])
def test_query_tiles_of_a_ragged_launch(qw, form):
    """Live rows to the ragged tests' tolerance against the reference, dead
    rows (and the tiles never computed) exactly 0; with the statistics, with
    ``-1`` holes, over int8 pages (float32 queries: tiles of 8) and at a
    query group of 6."""
    kw = dict(_TILE_FORMS[form])
    pack, stats = kw.pop("pack"), kw.pop("stats", False)
    atol, quant = kw.pop("atol", 2e-5), kw.pop("quant", False)
    case, q_lens = _tile_case(qw, **kw)
    q = case[0]
    g = pack * kw["heads"][0] // kw["heads"][1]
    tile = pa.query_tile(qw * g, q.dtype)
    assert tile == (16 if q.dtype == jnp.bfloat16 else 8) < qw * g
    if quant:
        case = (q, _quantize(case[1]), _quantize(case[2])) + case[3:]
    out = np.asarray(_assert_matches_reference(case, atol=atol, stats=stats,
                                               pack=pack), np.float32)
    for i, n in enumerate(q_lens):
        assert np.all(out[i, n:] == 0), i
    assert np.abs(out[6, :qw]).max() > 0


@pytest.mark.parametrize("qw", [64, 8])
def test_query_tiles_under_a_window(qw):
    """The same launch with a lower bound a query (a window of 40 positions
    in pages of 16: the bound lies inside a page): the first tile's mask is
    the whole tile's over its rows."""
    (q, pk, pv, tables, kv_lens, q_lens), lens = _tile_case(
        qw, heads=(4, 2), head_dim=64)
    kw = dict(q_lens=q_lens, layer=1, window=40)
    want = pa.paged_attention(q, pk, pv, tables, kv_lens, backend="xla", **kw)
    got = np.asarray(pa.paged_attention(q, pk, pv, tables, kv_lens,
                                        backend="pallas", **kw))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=2e-5)
    for i, n in enumerate(lens):
        assert np.all(got[i, n:] == 0), i


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_decode_row_in_a_wide_launch_is_the_decode_form(dtype):
    """Rows of ``q_len`` 1 in a launch 64 wide (the first tile of the eight
    or sixteen computed) against the same rows in the decode form."""
    q3, pk, pv, tables, kv_lens, _ = _group_case(
        5, [300, 129, 1, 77, 0], heads=(20, 20), head_dim=64, dtype=dtype)
    pk, pv = _pack(pk, 2), _pack(pv, 2)
    dec = pa.paged_attention(q3, pk, pv, tables, kv_lens, layer=1,
                             backend="pallas")
    wide = jnp.zeros((5, 64) + q3.shape[1:], dtype).at[:, 0].set(q3)
    q_lens = jnp.asarray([1, 1, 1, 1, 0], jnp.int32)
    out = np.asarray(pa.paged_attention(wide, pk, pv, tables, kv_lens,
                                        q_lens=q_lens, layer=1,
                                        backend="pallas"), np.float32)
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out[:4, 0], np.asarray(dec, np.float32)[:4],
                               atol=atol, rtol=atol)
    assert np.all(out[:, 1:] == 0) and np.all(out[4] == 0)


# (Q * g, queries' dtype) -> rows of a query tile
_QUERY_TILES = {
    "gpt2-large-decode": ((2, jnp.bfloat16), 2),
    "gpt2-large-w8": ((16, jnp.bfloat16), 16),      # one tile: today's body
    "gpt2-large-w64": ((128, jnp.bfloat16), 16),
    "gpt2-large-w64-f32": ((128, jnp.float32), 8),
    "trinity-decode": ((6, jnp.bfloat16), 6),
    "trinity-w4": ((24, jnp.bfloat16), 24),         # 16 does not divide 24
    "trinity-w64": ((384, jnp.bfloat16), 16),
    "f32-w2-g6": ((12, jnp.float32), 12),
    "f32-w4-g6": ((24, jnp.float32), 8),
}


@pytest.mark.parametrize("shape", list(_QUERY_TILES))
def test_query_tile_rule(shape):
    (qg, dtype), want = _QUERY_TILES[shape]
    assert pa.query_tile(qg, dtype) == want
    assert qg % want == 0


# q_lens, g, (Q * g, tile) -> the tiles the kernel computes a row
_TILES_COMPUTED = {
    "w64-g2": ([0, 1, 1, 7, 8, 9, 17, 64], 2, (128, 16),
               [0, 1, 1, 1, 1, 8, 8, 8]),
    "w32-g2-f32": ([0, 1, 4, 5, 32], 2, (64, 8), [0, 1, 1, 8, 8]),
    "w64-g6": ([1, 2, 3, 64], 6, (384, 16), [1, 1, 24, 24]),
    "one-tile": ([0, 1, 5, 8], 2, (16, 16), [0, 1, 1, 1]),
}


@pytest.mark.parametrize("shape", list(_TILES_COMPUTED))
def test_query_tiles_computed_is_a_hand_count(shape):
    q_lens, g, (qg, tile), want = _TILES_COMPUTED[shape]
    assert list(pa.query_tiles_computed(q_lens, g, qg, tile)) == want


@pytest.mark.parametrize("qw", [1, 4, 8, 64])
def test_a_launch_of_one_tile_traces_to_the_body_it_had(qw):
    """``Q * g`` up to one tile (every decode program, GPT-2 large's chunks
    up to 8): the three ``pl.when`` of the parent's body and nothing else.
    Wider: two more, the first tile alone or the whole; never a loop."""
    import re

    q = jnp.zeros((2, qw, 4, 64), jnp.bfloat16)
    pages = jnp.zeros((1, 8, 2, 16, 128), jnp.bfloat16)     # g = 2 packed
    tables = jnp.zeros((2, 4), jnp.int32)
    lens = jnp.asarray([5, 9], jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: pa.paged_attention(
        *a, q_lens=jnp.minimum(lens, qw), backend="pallas",
        interpret=False))(q, pages, pages, tables, lens))
    assert "tnn_paged_attention" in text
    assert len(re.findall(r"\bcond\[", text)) == (5 if qw * 2 > 16 else 3)
    assert not re.findall(r"\bwhile\[", text)


def test_attn_query_tile_share_is_a_hand_count():
    """``summary()["attn_query_tile_share"]``: over the paged steps wider
    than one query tile, the tiles the kernel computes over the tiles the
    launches hold, with the kernel module's own tile rule."""
    from tnn_tpu.models.gpt2 import GPT2
    from tnn_tpu.serving import InferenceEngine

    model = GPT2(vocab_size=128, max_len=512, num_layers=1, d_model=32,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    eng = InferenceEngine(model, params, num_blocks=40, block_size=16,
                          max_batch_size=4, max_seq_len=512)
    # both heads in ONE page row: a query group of 2, bf16 queries
    assert eng.pool.page_shape == (1, 40, 1, 16, 32)
    assert eng.pool.dtype == jnp.bfloat16
    assert "attn_query_tile_share" not in eng.metrics.summary()
    # a decode step, and a step 8 wide (16 query rows), are one tile wide:
    # nothing to leave out, nothing counted
    eng._observe_attention([None] * 4, np.array([1, 128, 129, 300]), 1)
    eng._observe_attention([None] * 2, np.array([9, 40, 0, 0]), 8,
                           np.array([8, 3, 0, 0]))
    assert "attn_query_tile_share" not in eng.metrics.summary()
    # 64 wide: 8 tiles of 16 a row; rows of 1, 1, 7 and 64 tokens are 2, 2,
    # 14 and 128 query rows: the first tile, three times, and all 8, of the
    # 32 held
    q_lens = np.array([1, 1, 7, 64])
    eng._observe_attention([None] * 4, 100 + q_lens, 64, q_lens)
    assert eng.metrics.summary()["attn_query_tile_share"] \
        == pytest.approx(11 / 32)
    # 16 wide: 2 tiles a row; an empty row computes none, 18 query rows both
    q_lens = np.array([1, 0, 16, 9])
    eng._observe_attention([None] * 3, 50 + q_lens, 16, q_lens)
    assert eng.metrics.summary()["attn_query_tile_share"] \
        == pytest.approx((11 + 1 + 0 + 2 + 2) / (32 + 8))
    fams = {f["name"]: f["samples"][0][-1]
            for f in eng.metrics.prometheus_series()}
    assert fams["tnn_serve_attn_query_tiles_computed_total"] == 16
    assert fams["tnn_serve_attn_query_tiles_total"] == 40
    # and the engine feeds it: a 20-token prompt goes out 32 wide (4 tiles
    # a row, 4 rows), its one live row's 40 query rows past the first tile:
    # all 4
    eng.submit(np.arange(20, dtype=np.int32), 3)
    eng.run_until_complete()
    assert eng.metrics.attn_query_tiles_held == 40 + 16
    assert eng.metrics.attn_query_tiles_computed == 16 + 4
