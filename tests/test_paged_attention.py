"""Parity tests for the ragged paged-attention decode kernel.

The Pallas kernel (``ops/pallas/paged_attention``) runs in interpret mode on
CPU (forced by the ``kernel`` marker's conftest fixture), checked against the
XLA-lax reference in the same module; the reference itself is checked against
a dense softmax-attention oracle built here. Covers ragged lengths, block
sizes, GQA head ratios, layer selection, zero-length rows, and the
``scatter_kv_rows`` write half of the page contract.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tnn_tpu.ops.pallas import paged_attention as pa

pytestmark = pytest.mark.kernel


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
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (4, 1)],
                         ids=["mha", "gqa2", "mqa"])
@pytest.mark.parametrize("qw", [4, 8])
def test_multitoken_kernel_matches_oracle(block_size, heads, qw):
    """Ragged q chunks x GQA ratios x block sizes: the kernel, the XLA
    reference, and the dense oracle agree; scatter_kv_chunk writes the
    chunk's KV where attention then reads it."""
    h, hkv = heads
    q, pk, pv, tables, starts, q_lens, rows_k, rows_v = _random_chunk_case(
        block_size * 100 + h * 10 + qw, block_size=block_size, num_heads=h,
        num_kv_heads=hkv, qw=qw)
    kv_lens = starts + q_lens
    pk = pa.scatter_kv_chunk(pk, tables, starts, rows_k, q_lens, layer=1)
    pv = pa.scatter_kv_chunk(pv, tables, starts, rows_v, q_lens, layer=1)
    ref = pa.paged_attention_reference(q, pk, pv, tables, kv_lens,
                                       q_lens=q_lens, layer=1)
    out = pa.paged_attention(q, pk, pv, tables, kv_lens, q_lens=q_lens,
                             layer=1, backend="pallas")
    oracle = _dense_oracle_mq(q, pk, pv, tables, kv_lens, q_lens, 1)
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
                holes=False, all_scratch=False):
    """A pool, tables that keep the engine's one-writer invariant (every
    non-scratch page in one row's table only, scratch page 0 as padding) and
    a ragged chunk per row: row 0 absent (q_lens 0), row 1 a full chunk that
    starts on a page's last slot (the most pages a chunk can straddle), the
    last row padding whose table is all scratch."""
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
}


@pytest.mark.parametrize("form", ["rows", "chunk"])
@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_page_write_matches_per_row_scatter(case, form):
    """scatter_kv_rows / scatter_kv_chunk (whole pages, in place) against the
    per-row scatter formula they replaced: bit-exact on every non-scratch
    page, whatever lands in the scratch page."""
    pages, tables, starts, rows, q_lens = _write_case(
        sum(map(ord, case)), **_WRITE_CASES[case])
    if case.startswith("int8"):
        pages = _quantize(pages)
    layer = 1
    if case == "one_layer":
        pages, layer = pages[1], None
    if form == "rows":
        # the decode form: every row writes one position (a padding row's
        # table is all scratch)
        args, new, old = (starts, rows[:, 0]), pa.scatter_kv_rows, _oracle_rows
    else:
        args, new, old = (starts, rows, q_lens), pa.scatter_kv_chunk, \
            _oracle_chunk
    if case == "traced_layer":
        got = jax.jit(lambda p, ly: new(p, tables, *args, layer=ly))(
            pages, jnp.asarray(layer, jnp.int32))
    else:
        got = new(pages, tables, *args, layer=layer)
    _assert_same_but_scratch(got, old(pages, tables, *args, layer=layer))
    if case == "all_scratch":   # and nothing but the scratch page changed
        _assert_same_but_scratch(got, pages)
