"""The paged KV write stays in place: what the compiled step programs hold,
and the engine invariant the page write relies on.

``scatter_kv_rows`` / ``scatter_kv_chunk`` (ops/pallas/paged_attention.py)
rewrite the sublane tiles of the donated pool that hold a step's rows
(``tnn_kv_row_write``; whole pages off the chip and for rows that do not
fill the lanes: GPT-2's unpacked int8 pool, a scale sidecar), in the
layout the paged kernel reads. Three things can silently undo that, and none
shows on the CPU:

* a write whose lowering makes the TPU compiler re-lay the WHOLE pool out
  around every layer's scatter (it did: 72 pool copies a decode step, 81% of
  the step). ``test_step_program_has_no_pool_copy_per_layer`` AOT-compiles
  the step programs for the compile-only v5e target and counts them.
* a write that falls back to moving whole pages: a gather, a select and a
  scatter of ``bs`` rows for one. ``_assert_row_writes`` holds every bf16
  program compiled here to the kernel, aliased in and out, inside its scope.
* a step that writes one non-scratch page from two rows, or a page someone
  else still reads: the write of a tile or a page would then lose a row. The engine
  never builds such a step (``PagedKVPool.check_step_writes``); the tests at
  the bottom drive it through the copy-on-write case and check every step.

Parity of the write itself is in tests/test_paged_attention.py.
"""
import re
import signal
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tnn_tpu.ops.pallas import paged_attention as pa
from tnn_tpu.serving import InferenceEngine
from tnn_tpu.serving.kv_pool import PagedKVPool
from tnn_tpu.utils import compile_cache

# gpt2-large as published, and the benchmark's pool (chipbench/configs/
# gpt2-large-serve.json): 20 heads of 64, pages of 16, 1,024 blocks, 16 rows
_WIDTHS = dict(vocab_size=50257, max_len=1024, d_model=1280, num_heads=20)
_HEADS, _HEAD_DIM, _BLOCK, _BLOCKS, _ROWS = 20, 64, 16, 1024, 16
# what the entry and exit of an int8 pool's program may cost: its rows of
# 64 one-byte values rest in another layout than the kernel reads (2 copies
# in + 2 out). A bf16 pool packs two heads a row (``pa.lane_pack``), rests
# in the kernel's layout, and may cost NOTHING
_ENTRY_EXIT_COPIES = {"bf16": 0, "int8": 6}
_KERNEL_LAYOUT = "{4,3,2,1,0"
_COMPILE_TIMEOUT_S = 240


class _Timeout(Exception):
    pass


@pytest.fixture
def alarm():
    """The compile-only target loads libtpu; a second process that holds it
    can make that wait. Fail after a bound instead of hanging a worker."""
    def on_alarm(signum, frame):
        raise _Timeout(f"no result in {_COMPILE_TIMEOUT_S} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(_COMPILE_TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    can never be read back here; conftest re-arms the cache for the next
    test."""
    compile_cache.disable()
    yield


# bytes of temporaries of each step program compiled here as PR 45 left it,
# with the whole-page write (``compiled.memory_analysis()``): the row write
# may not grow them. (It shrinks all but two, the windowed decode step from
# 19.4 MB to 1.5 MB; the shortcut and the two-group prompt steps lay a
# chunk's rows out by tile and take 66 KB and 130 KB more, under a
# thousandth, which is the room given.)
_TEMP_BYTES = {
    "gpt2-decode-bf16-2": 132_038_656,
    "gpt2-decode-bf16-4": 134_554_624,
    "gpt2-decode-int8-2": 970_429_440,
    "gpt2-decode-int8-4": 2_151_540_224,
    "gpt2-chunk64-bf16-2": 131_812_864,
    "gpt2-chunk64-bf16-4": 136_019_968,
    "gpt2-chunk64-int8-2": 975_361_536,
    "gpt2-chunk64-int8-4": 2_160_438_784,
    "windowed-decode": 19_357_696,
    "windowed-chunk256": 112_599_552,
    "latent-decode": 5_967_360,
    "latent-chunk64": 149_759_488,
    "shortcut-decode": 25_439_232,
    "shortcut-chunk32": 446_145_536,
    "two-group-decode": 9_225_728,
    "two-group-chunk64": 147_852_800,
    "state-decode": 12_902_400,
    "state-chunk32": 208_057_344,
    # PR 49's own first readings (granite-4.0-h-micro's layers, 24 rows); the
    # decode step's live ranges fit what the compiler counts as no
    # temporaries at all: a MB of room
    "ssm-decode": 1_000_000,
    "ssm-chunk64": 397_095_424,
}


def _compiled_text(lowered, program):
    """``lowered`` compiled for the described chip: its text, once its
    temporaries are held to what the program took with the page write."""
    compiled = lowered.compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.001 * _TEMP_BYTES[program], (program, temp)
    return compiled.as_text()


def _assert_row_writes(text, shape, calls, arrays, scope="kv_write",
                       reads_pages=False):
    """The compiled program ``text`` makes ``calls`` row writes under
    ``scope``, each ``tnn_kv_row_write`` over the pool of ``shape`` aliased
    in and out; nothing else under that scope holds pages (no gather, select
    or scatter of ``(.., H / p, bs, p * Dh)``; not asked of a scope that
    ``reads_pages``: EVA's read-back of a chunk's rows); and the program's
    ``arrays`` pool arguments are donated through."""
    lines = [x for x in text.splitlines() if f"/{scope}/" in x]
    dims = ",".join(map(str, shape))
    # the write is one jitted function, inlined under each site's scope
    writes = [x for x in lines if "custom-call(" in x
              and f"/{scope}/jit(_write_rows_pallas)/tnn_kv_row_write/" in x]
    assert len(writes) == calls, (len(writes), calls)
    for x in writes:
        assert re.search(r"= \w+\[%s\]" % dims, x), x[:200]
        assert "output_to_operand_aliasing={{}: (3, {})}" in x, x[:400]
    page = re.compile(r"= \w+\[[\d,]*%s\]" % ",".join(map(str, shape[2:])))
    moved = [x.strip()[:200] for x in lines
             if "custom-call(" not in x and page.search(x)]
    assert reads_pages or not moved, moved
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"\(\d+, \{\}, may-alias\)", header)) >= arrays, \
        header[:300]


def _pool_copies(one_chip, form, dtype, num_layers):
    from tnn_tpu.models.gpt2 import GPT2

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    model = GPT2(num_layers=num_layers, **_WIDTHS)
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    # the shape is the pool's own (a pool of two blocks says what a page is)
    shape = PagedKVPool(
        num_layers, _HEADS, _HEAD_DIM, 2, _BLOCK, dtype=jnp.bfloat16,
        kv_dtype="int8" if dtype == "int8" else "f32").page_shape
    shape = shape[:1] + (_BLOCKS,) + shape[2:]
    if dtype == "int8":
        pages = pa.QuantPages(spec(shape, jnp.int8),
                              spec(shape[:-1] + (1,), jnp.float32))
    else:
        assert shape[2:] == (_HEADS // 2, _BLOCK, 2 * _HEAD_DIM)
        pages = spec(shape, jnp.bfloat16)
    tables = spec((_ROWS, _WIDTHS["max_len"] // _BLOCK), jnp.int32)
    lens = spec((_ROWS,), jnp.int32)
    # the "auto" routes ask jax.default_backend(): say what the target is
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(model.apply_decode_paged,
                              donate_argnums=(2, 3)).lower(
                params, spec((_ROWS,), jnp.int32), pages, pages, tables, lens)
        else:
            lowered = jax.jit(model.apply_paged, donate_argnums=(2, 3)).lower(
                params, spec((_ROWS, 64), jnp.int32), pages, pages, tables,
                lens, lens)
        text = _compiled_text(lowered, f"gpt2-{form}-{dtype}-{num_layers}")
    assert "tnn_paged_attention" in text, "the Pallas kernel is not in it"
    dims = ",".join(map(str, shape))
    pool = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\(" % dims)
    # the layouts the program's pool ARGUMENTS rest in (K and V): the
    # module's first line, ``entry_computation_layout={(args)->(results)}``
    layouts = re.findall(r"\w+\[%s\](\{[^}]*\})" % dims,
                         text.split("\n", 1)[0].split(")->(")[0])
    # K and V a layer, the kernel; an int8 pool of heads of 64, unpacked: none
    if dtype == "bf16":
        _assert_row_writes(text, shape, 2 * num_layers, 2)
    else:
        assert "tnn_kv_row_write" not in text
    return [line.strip()[:160] for line in text.splitlines()
            if pool.search(line)], layouts


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("form", ["decode", "chunk64"])
def test_step_program_has_no_pool_copy_per_layer(form, dtype, one_chip,
                                                 no_compile_cache, alarm):
    """Pool-shaped ``copy`` instructions in the step program compiled for the
    v5e: as many at 4 layers as at 2 (none belongs to a layer), and no more
    than the program's entry and exit cost: NONE for the bf16 pool, whose
    arguments rest in the layout the kernel reads, and whose every write is
    ``tnn_kv_row_write`` inside ``kv_write``, aliased in and out, with no
    page gathered beside it (``_assert_row_writes``). (Of an int8 pool this
    counts the int8 data array; its f32 scale sidecar has another shape and
    its own copies: PERF.md section 7.)"""
    two, _ = _pool_copies(one_chip, form, dtype, 2)
    four, layouts = _pool_copies(one_chip, form, dtype, 4)
    assert len(two) == len(four), (two, four)
    assert len(four) <= _ENTRY_EXIT_COPIES[dtype], four
    if dtype == "bf16":
        assert len(layouts) == 2, layouts
        assert all(x.startswith(_KERNEL_LAYOUT) for x in layouts), layouts


@pytest.mark.parametrize("qw", [1, 64])
def test_paged_kernel_compiles_at_plain_llama_shapes(qw, one_chip,
                                                     no_compile_cache, alarm):
    """The kernel alone at widths no cell runs it at: heads of 128 and
    grouped queries (32 query heads over 8 KV heads, ``g`` = 4), the decode
    form and a 64-token chunk. The chip's compiler accepts the grouped grid
    step there: 8 pages of all 8 heads, ``Q * g`` = 4 and 256 query rows."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, heads, kv_heads, head_dim, blocks = 8, 32, 8, 128, 256
    pages = spec((2, blocks, kv_heads, _BLOCK, head_dim), jnp.bfloat16)
    tables, lens = spec((rows, 128), jnp.int32), spec((rows,), jnp.int32)
    assert pa.fetch_group(bs=_BLOCK, dh=head_dim, hkv=kv_heads,
                          qg=qw * heads // kv_heads,
                          page_dtype=jnp.bfloat16, nb=128) == (8, kv_heads)

    def attend(q, pk, pv, tbl, kv_lens, q_lens):
        return pa.paged_attention(q, pk, pv, tbl, kv_lens, q_lens=q_lens,
                                  layer=1, backend="pallas", interpret=False)

    text = jax.jit(attend).lower(
        spec((rows, qw, heads, head_dim), jnp.bfloat16), pages, pages,
        tables, lens, lens).compile().as_text()
    assert "tnn_paged_attention" in text


# (what, (B, H, Sq, Dh), Skv, causal): the calls ``flash_attention`` gets
_FLASH_CALLS = [
    ("the training cell", (8, 16, 1024, 64), 1024, True),   # 4 x 4 sub-tiles
    ("a grid of blocks", (2, 16, 4096, 64), 4096, True),    # whole blocks
    ("a padded sequence", (2, 12, 1100, 64), 1100, True),
    ("an encoder", (8, 12, 197, 64), 197, False),           # ViT, not causal
    ("a cached prompt", (2, 12, 512, 64), 1024, True),      # a traced offset
]


@pytest.mark.parametrize("what,shape,skv,causal", _FLASH_CALLS,
                         ids=[c[0].replace(" ", "_") for c in _FLASH_CALLS])
def test_flash_kernels_compile_for_the_chip(what, shape, skv, causal,
                                            one_chip, no_compile_cache,
                                            alarm):
    """The forward and the fused backward as the chip's compiler gets them:
    the statically unrolled sub-tile walk of a one-block call, and the
    whole-block forms under ``pl.when`` of every other (the interpreter on
    the CPU accepts slices and concatenations that Mosaic may refuse)."""
    from tnn_tpu.ops.pallas.flash_attention import flash_attention

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, h, sq, d = shape
    offset = (spec((), jnp.int32),) if sq != skv else ()

    def loss(q, k, v, *off):
        return flash_attention(q, k, v, causal=causal,
                               kv_offset=off[0] if off else None).astype(
                                   jnp.float32).sum()

    with mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            spec(shape, jnp.bfloat16), spec((b, h, skv, d), jnp.bfloat16),
            spec((b, h, skv, d), jnp.bfloat16), *offset).compile().as_text()
    assert "tnn_flash_fwd" in text and "tnn_flash_bwd_fused" in text


# -- the row write where no cell runs it: a wide batch, and across chips -----


@pytest.mark.parametrize("page,batch", [((32, 128, 128), 128),
                                        ((10, 16, 128), 512)],
                         ids=["evabyte_x128", "gpt2_large_x512"])
def test_row_write_compiles_at_a_wide_decode_batch(page, batch, one_chip,
                                                   no_compile_cache, alarm):
    """``--max-batch-size`` has no cap, and a decode step's new rows, ONE
    sublane each, pad to a tile's 16 in VMEM: whole there these two would
    take 16 and 20 MiB and the chip's compiler refuses them. Past half the
    write's budget the rows come by DMA beside their tiles instead
    (``_write_rows_pallas``), and the program compiles."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    shape = (2, 2 * batch) + page
    assert batch * page[0] * pa._tile_bytes(1, page[2], jnp.bfloat16) \
        > pa._WRITE_VMEM

    def write(pages, tables, starts, rows):
        return pa.scatter_kv_rows(pages, tables, starts, rows, layer=1)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        text = jax.jit(write, donate_argnums=(0,)).lower(
            spec(shape, jnp.bfloat16), spec((batch, 8), jnp.int32),
            spec((batch,), jnp.int32),
            spec((batch, page[0], page[2]), jnp.bfloat16)).compile().as_text()
    # GPT-2's page of 16 rows is one tile: the rows laid out by tile have a
    # page's shape there, and are not one
    _assert_row_writes(text, shape, 1, 1, reads_pages=page[1] == 16)


@pytest.mark.parametrize("qw", [1, 64])
@pytest.mark.parametrize("dtype,bs", [("int8", 32), ("int8", 128),
                                      ("float32", 16), ("float32", 128)])
def test_row_write_compiles_at_every_tile_height(dtype, bs, qw, one_chip,
                                                 no_compile_cache, alarm):
    """The tile follows the dtype (16 rows of bf16 in every program above):
    a float32 pool's is 8 rows, and an int8 pool's data, where its rows fill
    the lanes, goes through the kernel at 32 while its scale sidecar, one
    lane wide, keeps the whole-page form (``_kernel_writes`` asks each
    array). Mosaic takes both, aliased, with no copy of the pool's data."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    shape = (2, 64, 8, bs, 128)
    if dtype == "int8":
        pages = pa.QuantPages(spec(shape, jnp.int8),
                              spec(shape[:-1] + (1,), jnp.float32))
        rows = spec((8, qw, 8, 128), jnp.bfloat16)
    else:
        pages, rows = spec(shape, jnp.float32), spec((8, qw, 8, 128),
                                                     jnp.float32)

    def write(pages, tables, starts, rows, q_lens):
        return pa.scatter_kv_chunk(pages, tables, starts, rows, q_lens,
                                   layer=1)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        text = jax.jit(write, donate_argnums=(0,)).lower(
            pages, spec((8, 8), jnp.int32), spec((8,), jnp.int32), rows,
            spec((8,), jnp.int32)).compile().as_text()
    _assert_row_writes(text, shape, 1, 2 if dtype == "int8" else 1,
                       reads_pages=dtype == "int8")
    # (the scale sidecar has its own copies, as before: PERF.md section 7)
    pool = re.compile(r"= \w+\[2,64,8,%d,128\]\{[^}]*\} copy\(" % bs)
    assert not [x.strip()[:160] for x in text.splitlines() if pool.search(x)]


def _mesh_program(kind, form, heads):
    """A GPT-2 of ``heads`` heads of 64 at 2 layers, the step
    ``serving/tp.py`` / ``serving/sp.py`` run under ``shard_map``, compiled
    for the described 2x2 host: (the program's text, ONE shard's pool
    shape). A tensor-parallel shard holds a quarter of the heads, packed by
    what IT holds (``PagedKVPool.lane_pack``); a sequence-parallel shard a
    quarter of the blocks."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tnn_tpu.models.gpt2 import GPT2
    from tnn_tpu.parallel import mesh as mesh_lib
    from tnn_tpu.serving import sp as sp_lib, tp as tp_lib

    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    head_dim, rows, blocks = 64, 8, 256
    model = GPT2(vocab_size=50257, max_len=1024, num_layers=2,
                 d_model=heads * head_dim, num_heads=heads)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"])
    if kind == "tp":
        mesh = mesh_lib.make_mesh(model=4, devices=devices)
        sharded, page_spec = tp_lib.TPModel(model, 4), tp_lib.PAGE_SPEC
        param_specs = jax.tree_util.tree_map_with_path(tp_lib._spec_for,
                                                       params)
        p = pa.lane_pack(heads // 4, head_dim, jnp.bfloat16)
    else:
        mesh = mesh_lib.make_mesh(seq=4, devices=devices)
        sharded, page_spec = sp_lib.SPModel(model, 4), sp_lib.PAGE_SPEC
        param_specs = jax.tree_util.tree_map(lambda _: P(), params)
        p = pa.lane_pack(heads, head_dim, jnp.bfloat16)
    shape = (2, blocks, heads // p, _BLOCK, p * head_dim)

    def spec(shape, dt, at=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, at))

    args = [jax.tree_util.tree_map(
                lambda x, at: spec(x.shape, x.dtype, at), params, param_specs),
            spec((rows,) if form == "decode" else (rows, 64), jnp.int32),
            spec(shape, jnp.bfloat16, page_spec),
            spec(shape, jnp.bfloat16, page_spec),
            spec((rows, 64), jnp.int32), spec((rows,), jnp.int32)]
    fn = sharded.apply_decode_paged
    if form != "decode":
        fn, args = sharded.apply_paged, args + [spec((rows,), jnp.int32)]
    in_specs = (param_specs, P(), page_spec, page_spec) + (P(),) * (
        len(args) - 4)
    body = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), page_spec, page_spec),
                         check_vma=False)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        text = jax.jit(body, donate_argnums=(2, 3)).lower(
            *args).compile().as_text()
    return text, NamedSharding(mesh, page_spec).shard_shape(shape)


_COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|all-to-all|"
                         r"collective-permute|reduce-scatter)")


@pytest.mark.parametrize("form", ["decode", "chunk64"])
@pytest.mark.parametrize("kind", ["tp", "sp"])
def test_sharded_step_writes_rows_in_place_on_every_chip(
        kind, form, no_compile_cache, alarm):
    """Under ``shard_map`` the kernel sees ONE shard's pages (16 heads: 4 a
    tensor-parallel shard, two packed rows of 128 lanes): every layer's K
    and V write of the tensor- and the sequence-parallel step is
    ``tnn_kv_row_write`` over the shard's own pool, aliased in and out, with
    no page gathered beside it and no pool copy; and no collective stands
    inside ``kv_write`` (the steps' own are the attention's and the MLP's
    sums, elsewhere)."""
    text, local = _mesh_program(kind, form, 16)
    assert local[-1] == 128
    _assert_row_writes(text, local, 4, 2)
    pool = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\("
                      % ",".join(map(str, local)))
    assert not [x.strip()[:160] for x in text.splitlines() if pool.search(x)]
    assert _COLLECTIVE.search(text), "a sharded step with no collective"
    assert not [x.strip()[:160] for x in text.splitlines()
                if "/kv_write/" in x and _COLLECTIVE.search(x)]


@pytest.mark.parametrize("form", ["decode", "chunk64"])
def test_shard_of_half_filled_lanes_keeps_the_page_form(
        form, no_compile_cache, alarm):
    """gpt2_small over four chips (``chip_smoke.py``'s four-chip phase): a
    shard's 3 heads of 64 cannot pack, its page rows fill half the lanes,
    and Mosaic refuses a tile of them ("slice shape along dimension 4 must
    be aligned to tiling (128), but is 64"). Such a pool keeps the
    whole-page write, chosen on the pages' shape, and the step compiles."""
    text, local = _mesh_program("tp", form, 12)
    assert local[2:] == (3, _BLOCK, 64)
    assert "tnn_kv_row_write" not in text and "/kv_write/" in text
    assert "tnn_paged_attention" in text


# -- the windowed model's step (PR 28): the same question at its widths -------


def _eva_program(one_chip, form, num_layers, blocks=64):
    """EvaByte's block as published (32 heads of 128, window 2,048, chunk
    16), pages of 128 as the benchmark serves it, 8 rows, compiled for the
    v5e: (the program's text, its pool's shape)."""
    from tnn_tpu import models

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    model = models.create("evabyte", num_layers=num_layers)
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    shape = (num_layers, blocks, 32, 128, 128)
    pages = spec(shape, jnp.bfloat16)
    tables, lens = spec((8, 32), jnp.int32), spec((8,), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(model.apply_decode_paged,
                              donate_argnums=(2, 3)).lower(
                params, spec((8,), jnp.int32), pages, pages, tables, lens)
        else:
            lowered = jax.jit(model.apply_paged, donate_argnums=(2, 3)).lower(
                params, spec((8, 256), jnp.int32), pages, pages, tables,
                lens, lens)
        return _compiled_text(lowered, f"windowed-{form}"), shape


@pytest.mark.parametrize("form", ["decode", "chunk256"])
def test_windowed_step_compiles_for_the_chip_with_no_pool_copy(
        form, one_chip, no_compile_cache, alarm):
    """The kernel ``tnn_eva_attention`` at heads of 128 is accepted by the
    chip's compiler in its decode and its chunk form, and the step around it
    (exact rows written, summaries read back and written, both under
    donation) makes NO pool-shaped copy: a row of 128 fills the lanes, so
    this pool needs no conversion at entry or exit either."""
    text, shape = _eva_program(one_chip, form, 2)
    assert "tnn_eva_attention" in text and "tnn_paged_attention" not in text
    pool = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\("
                      % ",".join(map(str, shape)))
    assert not [line for line in text.splitlines() if pool.search(line)]
    # the exact rows and the summaries, K and V, each of the 2 layers
    _assert_row_writes(text, shape, 4, 2)
    _assert_row_writes(text, shape, 4, 2, scope="eva_summarise",
                       reads_pages=True)


# -- the latent model's step (PR 32): the same question at its widths ---------


@pytest.mark.parametrize("form", ["decode", "chunk64"])
def test_latent_step_compiles_for_the_chip_with_no_pool_copy(
        form, one_chip, no_compile_cache, alarm):
    """Mistral Small 4's block as published (32 heads over latent rows of 256
    + 64 in 384 lanes, experts of 2,048 x 4,096; 4 of them held here so that
    the compile is quick), pages of 128, 32 rows, 2 layers: the chip's
    compiler accepts ``tnn_mla_attention`` and ``tnn_expert_gmm`` in the
    decode and the chunk form, and the step around them makes NO pool-shaped
    copy: a latent row fills whole lanes, so the ONE pool array rests in the
    layout its write and its kernel read, and the value stub rides along."""
    from tnn_tpu import models

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    model = models.create("mistral_small4", num_layers=2, held_experts=4)
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    shape = PagedKVPool(2, 1, model.latent_row, 2, 128, dtype=jnp.bfloat16,
                        latent=True).page_shape
    shape = shape[:1] + (512,) + shape[2:]
    assert shape == (2, 512, 1, 128, 384)
    pages, stub = spec(shape, jnp.bfloat16), spec((2, 1, 1, 8, 128),
                                                   jnp.bfloat16)
    tables, lens = spec((32, 256), jnp.int32), spec((32,), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(model.apply_decode_paged,
                              donate_argnums=(2, 3)).lower(
                params, spec((32,), jnp.int32), pages, stub, tables, lens)
        else:
            lowered = jax.jit(model.apply_paged, donate_argnums=(2, 3)).lower(
                params, spec((32, 64), jnp.int32), pages, stub, tables, lens,
                lens)
        text = _compiled_text(lowered, f"latent-{form}")
    assert "tnn_mla_attention" in text and "tnn_expert_gmm" in text
    assert "tnn_paged_attention" not in text
    dims = ",".join(map(str, shape))
    pool = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\(" % dims)
    assert not [line for line in text.splitlines() if pool.search(line)]
    layouts = re.findall(r"\w+\[%s\](\{[^}]*\})" % dims,
                         text.split("\n", 1)[0].split(")->(")[0])
    assert layouts and all(x.startswith(_KERNEL_LAYOUT) for x in layouts)
    _assert_row_writes(text, shape, 2, 1)      # ONE array, a write a layer


# -- the shortcut block's step (PR 41): two cache layers a block --------------------


@pytest.mark.parametrize("form", ["decode", "chunk32"])
def test_shortcut_step_compiles_for_the_chip_with_no_pool_copy(
        form, one_chip, no_compile_cache, alarm):
    """LongCat-Flash's block as published (64 heads over latent rows of 512 +
    64 in 640 lanes, both rank scales, two dense feed-forwards of 12,288, a
    router of 768 with 12 a token, experts of 2,048 x 6,144; 4 of them held
    here and ONE block so that the compile is quick), pages of 128, 64 rows:
    the chip's compiler accepts ``tnn_mla_attention`` at 64 heads and a row of
    640 (a chunk of 32 is four query tiles of 512 rows) and
    ``tnn_expert_gmm`` at this width, in the decode and the 64 x 32 mixed
    form, and the step around them makes NO pool-shaped copy of the pool's
    TWO layers, written one after the other by the block's two attentions.
    The mixed step's 2,048 tokens x 12 picks go through the held experts in
    8 slices of 256 tokens (a sorted buffer of 3,072 + 4 x 128 rows, not of
    24,576 + 4 x 128: ``nn.moe.SORTED_BYTES``)."""
    from tnn_tpu import models

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    model = models.create("longcat_flash_ep32", num_layers=1, held_experts=4)
    assert model.cache_layers == 2 and model.latent_row == 640
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    shape = PagedKVPool(2, 1, model.latent_row, 2, 128, dtype=jnp.bfloat16,
                        latent=True).page_shape
    shape = shape[:1] + (256,) + shape[2:]
    assert shape == (2, 256, 1, 128, 640)
    pages, stub = spec(shape, jnp.bfloat16), spec((2, 1, 1, 8, 128),
                                                   jnp.bfloat16)
    tables, lens = spec((64, 64), jnp.int32), spec((64,), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(model.apply_decode_paged,
                              donate_argnums=(2, 3)).lower(
                params, spec((64,), jnp.int32), pages, stub, tables, lens)
        else:
            lowered = jax.jit(model.apply_paged, donate_argnums=(2, 3)).lower(
                params, spec((64, 32), jnp.int32), pages, stub, tables, lens,
                lens)
        text = _compiled_text(lowered, f"shortcut-{form}")
    assert "tnn_mla_attention" in text and "tnn_expert_gmm" in text
    assert "tnn_paged_attention" not in text
    assert ("bf16[3584,6144]" in text) == (form == "chunk32") \
        and "bf16[25088,6144]" not in text
    _assert_row_writes(text, shape, 2, 1)      # a write a cache layer
    dims = ",".join(map(str, shape))
    pool = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\(" % dims)
    assert not [line for line in text.splitlines() if pool.search(line)]
    layouts = re.findall(r"\w+\[%s\](\{[^}]*\})" % dims,
                         text.split("\n", 1)[0].split(")->(")[0])
    assert layouts and all(x.startswith(_KERNEL_LAYOUT) for x in layouts)


# -- the two-group model's step (PR 37): both kinds of layer in one program -----


@pytest.mark.parametrize("form", ["decode", "chunk64"])
def test_two_group_step_compiles_for_the_chip_with_no_pool_copy(
        form, one_chip, no_compile_cache, alarm):
    """Trinity Large's block as published (48 query heads over 8 KV heads of
    128, window 4,096, dense layer of 12,288, experts of 3,072 x 3,072; 4 of
    them held here so that the compile is quick), its five layers (a dense
    sliding layer, sliding, sliding, full, sliding), pages of 128, 32 rows,
    the cell's table (289 global entries, 4 x 34 window entries, the base):
    the chip's compiler accepts the window kernel beside the plain one and
    the grouped expert product in the decode and the chunk form, and the
    step around them makes NO pool-shaped copy: ONE layer of pages serves
    both groups in the layout its write and its kernels read."""
    from tnn_tpu import models

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    model = models.create("trinity_large_ep8", held_experts=4)
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    pool = PagedKVPool(1, 8, 128, 2, 128, dtype=jnp.bfloat16,
                       groups=model.page_groups)
    shape = pool.page_shape[:1] + (1024,) + pool.page_shape[2:]
    assert shape == (1, 1024, 8, 128, 128)
    pages = spec(shape, jnp.bfloat16)
    width = pool.table_width(36992)
    assert width == 289 + 4 * 34 + 1
    tables, lens = spec((32, width), jnp.int32), spec((32,), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(model.apply_decode_paged,
                              donate_argnums=(2, 3)).lower(
                params, spec((32,), jnp.int32), pages, pages, tables, lens)
        else:
            lowered = jax.jit(model.apply_paged, donate_argnums=(2, 3)).lower(
                params, spec((32, 64), jnp.int32), pages, pages, tables, lens,
                lens)
        text = _compiled_text(lowered, f"two-group-{form}")
    names = set(re.findall(r"tnn_[a-z_]+[a-z]", text))
    assert {"tnn_paged_attention", "tnn_paged_attention_win",
            "tnn_expert_gmm"} <= names
    assert "tnn_mla_attention" not in names
    # five layers, K and V, into the ONE layer of pages both groups share
    _assert_row_writes(text, shape, 10, 2)
    dims = ",".join(map(str, shape))
    pool_copy = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\(" % dims)
    assert not [line for line in text.splitlines() if pool_copy.search(line)]
    layouts = re.findall(r"\w+\[%s\](\{[^}]*\})" % dims,
                         text.split("\n", 1)[0].split(")->(")[0])
    assert layouts and all(x.startswith(_KERNEL_LAYOUT) for x in layouts)


# -- the sampler's conditional (PR 35): the chip's compiler keeps it ----------


def test_sampler_sort_stands_inside_the_conditional(one_chip,
                                                    no_compile_cache, alarm):
    """``sampling.sample_ragged`` at a served vocabulary (Mistral Small 4's
    slice, 32 rows; the sort takes the compiler ~25 s, so one shape),
    compiled for the v5e: ONE ``conditional`` at the entry, which the
    compiler has not flattened into a ``select``, and the ONE sort of the
    vocabulary inside a branch of it: a step of greedy rows runs no sort."""
    from tnn_tpu.models.sampling import sample_ragged

    rows, vocab = 32, 32768

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = jax.jit(sample_ragged).lower(
        spec((rows, vocab), jnp.float32), spec((2,), jnp.uint32),
        spec((rows,), jnp.float32), spec((rows,), jnp.int32),
        spec((rows,), jnp.float32)).compile().as_text()
    blocks = [b.strip() for b in text.split("\n\n")
              if "{" in b.strip().split("\n", 1)[0]]
    entry = [b for b in blocks if b.startswith("ENTRY")]
    assert len(entry) == 1
    assert len(re.findall(r" conditional\(", entry[0])) == 1
    assert " sort(" not in entry[0]
    assert len(re.findall(r" sort\(", text)) == 1
    assert f"f32[{rows},{vocab}]" in re.search(r"[^\n]* sort\(",
                                               text).group(0)


# -- the one-writer invariant --------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    from tnn_tpu.models.gpt2 import GPT2

    model = GPT2(vocab_size=128, max_len=64, num_layers=2, d_model=32,
                 num_heads=2)
    return model, model.init(jax.random.PRNGKey(0), (1, 8))["params"]


@pytest.mark.parametrize("mode", ["sync", "overlap", "spec"])
def test_every_step_writes_a_page_from_one_row(tiny_lm, mode):
    """A shared-prefix fork followed by writes: twins of a published prompt
    (full-cover hits, whose last block is cloned before its first write) and
    sharers of its prefix run in ONE batch with the publisher's pages
    forked into their tables. Every packed step is held to the invariant:
    each non-scratch page it writes has refcount 1 and one writing row."""
    model, params = tiny_lm
    kw = dict(spec="ngram", spec_k=3) if mode == "spec" else {}
    eng = InferenceEngine(model, params, num_blocks=40, block_size=4,
                          max_batch_size=4, max_seq_len=32, prefix_cache=True,
                          overlap=mode != "sync", **kw)
    eng.pool.debug = True       # what TNN_POOL_DEBUG=1 sets
    steps, shared, refused = [], [], []
    inner = eng.pool.check_step_writes

    def checked(tables, starts, q_lens):
        try:
            inner(tables, starts, q_lens)
        except ValueError as e:     # the engine isolates a failed step
            refused.append(str(e))
            raise
        steps.append(int(np.sum(np.asarray(q_lens) > 0)))
        shared.append(sum(r > 1 for r in eng.pool._ref.values()))

    eng.pool.check_step_writes = checked
    rng = np.random.default_rng(5)
    base = np.tile(rng.integers(0, 128, 4), 2).astype(np.int32)  # 2 blocks
    first = eng.submit(base, 6)
    for _ in range(2):          # the publisher's prefill ends and publishes
        eng.step()
    tails = [rng.integers(0, 128, 3).astype(np.int32) for _ in range(2)]
    prompts = [base, base] + [np.concatenate([base, t]) for t in tails]
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.run_until_complete()
    eng.check_invariants()
    assert not refused, refused
    assert eng.metrics.prefix_cows >= 1, "no full-cover hit was cloned"
    assert len(steps) > 4 and max(steps) > 1
    assert max(shared) > 0, "no page was shared while steps wrote"
    assert out[rids[0]] == out[rids[1]] == out[first]


def test_check_step_writes_refuses_two_writers_and_shared_pages():
    pool = PagedKVPool(num_layers=1, num_blocks=8, num_kv_heads=1, block_size=4,
                       head_dim=8)
    a, b = pool.alloc(2), pool.alloc(1)
    scratch = PagedKVPool.SCRATCH
    tables = np.array([a, b + [scratch], [scratch, scratch]], np.int32)
    ones = np.ones(3, np.int32)
    pool.check_step_writes(tables, np.array([5, 2, 0]), ones)
    # rows 0 and 1 both write page a[1]
    twice = np.array([a, [b[0], a[1]], [scratch, scratch]], np.int32)
    with pytest.raises(ValueError, match="rows 0 and 1"):
        pool.check_step_writes(twice, np.array([5, 5, 0]), ones)
    # ... but only if both WRITE it: row 1 reading it is a shared prefix
    pool.fork([a[1]])
    pool.check_step_writes(twice, np.array([2, 0, 0]), ones)
    with pytest.raises(ValueError, match="refcount 2"):
        pool.check_step_writes(twice, np.array([5, 0, 0]), ones)
    # a chunk is held to every page it straddles; absent rows to none
    with pytest.raises(ValueError, match="refcount 2"):
        pool.check_step_writes(twice, np.array([2, 0, 0]),
                               np.array([4, 1, 0]))
    pool.check_step_writes(twice, np.array([5, 0, 0]), np.array([0, 1, 0]))


# -- state slots beside the pages (PR 44): no state-shaped copy either ---------


# what each state model's step is compiled from: its layers at published
# widths (few of them, so that the compile is quick), the pool's pages, the
# chunk's width, and the kernels its text must name (the state kernel in the
# decode form alone: a prompt chunk runs the chunked ``jax.numpy`` form)
_STATE_MODELS = {
    "state": dict(
        name="qwen3_next_ep4", kw=dict(num_layers=4, held_experts=4),
        rows=16, pages=(1, 256, 2, 128, 256), chunk=32, step="tnn_gdn_step",
        kernels={"tnn_paged_attention", "tnn_expert_gmm"}),
    "ssm": dict(
        # the cell's 24 rows: under ~2 MB the compiler would keep the conv
        # array in VMEM for the whole step, which is a copy in and out
        name="granite4_h_micro", rows=24, pages=(1, 256, 4, 128, 128),
        chunk=64,
        kw=dict(num_layers=4, layer_types=["mamba", "attention", "mamba",
                                           "mamba"]),
        step="tnn_mamba2_step",
        kernels={"tnn_paged_attention", "tnn_kv_row_write"})}


@pytest.mark.parametrize("family,form", [
    ("state", "decode"), ("state", "chunk32"), ("ssm", "decode"),
    ("ssm", "chunk64")])
def test_state_step_compiles_for_the_chip_with_no_pool_or_state_copy(
        family, form, one_chip, no_compile_cache, alarm):
    """(``ssm``: granite-4.0-h-micro's layers as published, PR 49: a Mamba-2
    mixer of 64 heads of 64 with a state of 128 on both sides of a plain
    attention layer of 32 query heads over 8 KV heads of 64, TWO heads a
    page row under grouped queries, which no chip had compiled; the conv
    rows rest as 102 rows of 128 lanes.) Qwen3-Next's layers as published (Gated DeltaNet: 16 key and 32 value
    heads of 128 behind a convolution of 4; full attention: 16 query heads
    over 2 KV heads of 256; experts of 512 x 2,048, 4 of them held here so
    that the compile is quick), one period (linear, linear, linear, full),
    pages of 128, 16 rows: the chip's compiler accepts ``tnn_gdn_step`` (the
    state aliased in and out, the snapshot's DMA under ``pl.when``) beside
    the paged kernel at heads of 256 and the grouped expert product, and the
    step around them copies NEITHER the pool NOR any of the four state
    arrays: slots are gathered and scattered a row at a time in the layout
    the arrays rest in."""
    from tnn_tpu import models

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    what = _STATE_MODELS[family]
    rows = what["rows"]
    model = models.create(what["name"], **what["kw"])
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), (1, 8))["params"]))
    assert model.cache_layers == 1
    pages = spec(what["pages"], jnp.bfloat16)
    group = model.state_group
    n = group["layers"]
    state = {
        "conv": spec((n, rows + 1) + group["conv"], jnp.bfloat16),
        "rec": spec((n, rows + 1) + group["rec"], jnp.float32),
        "conv_snap": spec((n, 2 * rows + 1) + group["conv"], jnp.bfloat16),
        "rec_snap": spec((n, 2 * rows + 1) + group["rec"], jnp.float32)}
    tables, lens = spec((rows, 9), jnp.int32), spec((rows,), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.dict("os.environ", {"TNN_PALLAS_INTERPRET": "0"}):
        if form == "decode":
            lowered = jax.jit(
                lambda p, t, pk, pv, tb, o, s: model.apply_decode_paged(
                    p, t, pk, pv, tb, o, state=s),
                donate_argnums=(2, 3, 6)).lower(
                params, spec((rows,), jnp.int32), pages, pages, tables, lens,
                state)
        else:
            lowered = jax.jit(
                lambda p, t, pk, pv, tb, o, q, s: model.apply_paged(
                    p, t, pk, pv, tb, o, q, state=s, head_at=q - 1),
                donate_argnums=(2, 3, 7)).lower(
                params, spec((rows, what["chunk"]), jnp.int32), pages, pages,
                tables, lens, lens, state)
        text = _compiled_text(lowered, f"{family}-{form}")
    names = set(re.findall(r"tnn_[a-z0-9_]+[a-z0-9]", text))
    assert what["kernels"] <= names
    assert (what["step"] in names) == (form == "decode")
    for shape in [pages.shape] + [s.shape for s in state.values()]:
        dims = ",".join(map(str, shape))
        copy = re.compile(r"= \w+\[%s\]\{[^}]*\} copy\(" % dims)
        assert not [ln for ln in text.splitlines() if copy.search(ln)], dims


# -- the engine's tokens, whichever form writes ---------------------------------


@pytest.mark.parametrize("name", ["gpt2_tiny", "evabyte_tiny",
                                  "mistral_small4_tiny", "trinity_large_tiny",
                                  "longcat_flash_tiny", "qwen3_next_tiny"])
def test_served_tokens_do_not_depend_on_the_write_form(name):
    """Each kind of pool the serving path has (packed K/V pages; window and
    summary pages; one array of latent rows, in one and in two cache layers
    a block; two page groups in one layer; pages beside state slots) served
    twice through the engine, prompts in chunks and then decode steps: with
    every write ``tnn_kv_row_write`` (interpreted here, the choice made for
    it: these pages are narrower than the chip's lanes) the tokens are those
    of the whole-page form."""
    from tnn_tpu import models

    model = models.create(name)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (37, 16, 50)]

    def serve(kernel):
        asked = []

        def form(pages):
            asked.append(pages.shape)
            return kernel

        with mock.patch.object(pa, "_kernel_writes", form):
            eng = InferenceEngine(model, params, num_blocks=96, block_size=8,
                                  max_batch_size=4, chunk_size=16,
                                  prefix_cache=False, max_seq_len=192)
            rids = [eng.submit(p, 40) for p in prompts]
            out = eng.run_until_complete()
            eng.check_invariants()
        return [out[r] for r in rids], asked

    want, _ = serve(False)
    got, asked = serve(True)
    assert asked, "no write asked for its form"
    assert got == want and all(len(t) == 40 for t in got)
