"""Ahead-of-time compiles for a described TPU v5e — what only the chip's
compiler can say, asked of it without a chip.

The suite runs on the CPU backend, where every kernel of the device path
compiles and agrees with its host twin; what that cannot show is whether
the *TPU* compiler accepts the same programs (a Pallas block not aligned to
the tiling, an int64 op with no lowering, a program that does not fit the
chip's memory). The TPU compiler is installed with jax and compiles for a
topology that is described, not attached, so these tests lower the device
programs at their production shapes with ``ShapeDtypeStruct`` arguments and
compile them. Nothing runs: a pass here is not a chip run (that is
``chip_smoke.py``).

The topology is described inside a module-scoped fixture and nowhere else:
only one process may hold the TPU library, the xdist workers each import
this file, and a call at import time (or in a ``skipif`` / ``parametrize``
argument) would make the workers collect different tests. Keep every
TPU-compile test in this one file for the same reason.

The sort-join is the one program whose compile is slow (70 s at a 64 Ki-row
record batch, ~150 s at the 10M-row bucket, against 2 s at 1024 rows): the
tier-1 cases compile it at a small bucket and the real widths are marked
``slow``. The windowed join that replaced it on the one-chip route compiles
in ~6 s at the 10M-row bucket and is compiled at its real widths in tier 1.
"""

import numpy as np
import pytest

from kart_tpu.diff.device_batch import DEVICE_BATCH_ROWS
from kart_tpu.ops.blocks import bucket_size
from kart_tpu.ops.diff_kernel import CLASSIFY_CHUNK_ROWS
from kart_tpu.parallel.mesh import FEATURES_AXIS


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: the next run
    # would warn and recompile, so the cache stays off around these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_x64", True)  # int64 keys, as lazy_jit sets
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", params=[1, 4], ids=["mesh1", "mesh4"])
def mesh(request, topo):
    from jax.sharding import Mesh

    return Mesh(np.asarray(topo.devices[: request.param]), (FEATURES_AXIS,))


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sharded(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(FEATURES_AXIS)), NamedSharding(mesh, P())


def _block_shapes(n, sharding, lead=()):
    """(keys, oids, count) of one padded block side."""
    return (
        _shape(lead + (n,), np.int64, sharding),
        _shape(lead + (n, 5), np.uint32, sharding),
        _shape(lead, np.int64, sharding),
    )


def _device_bytes(compiled):
    """Arguments + temporaries + outputs of one compiled program: what has
    to fit one v5e chip's 16 GB."""
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
    )


@pytest.mark.parametrize("n_envelopes", [65_536, 10_027_008])
def test_bbox_pallas_compiles(one_chip, n_envelopes):
    """The Pallas envelope scan at the small grid and at what 10M envelopes
    pad to (`pad_envelopes`): a real Mosaic kernel, not a jnp route."""
    import jax

    from kart_tpu.ops.bbox import _bbox_pallas_inner_core

    col = _shape((n_envelopes,), np.float32, one_chip)
    query = _shape((4,), np.float32, one_chip)
    with jax.enable_x64(False):  # as bbox_intersects_pallas runs it
        compiled = (
            jax.jit(_bbox_pallas_inner_core)
            .lower(col, col, col, col, query)
            .compile()
        )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "bucket",
    [
        1024,
        # a full chunk of the one-device route, and the merge's 8M-row chunk
        pytest.param(bucket_size(CLASSIFY_CHUNK_ROWS), marks=pytest.mark.slow),
        pytest.param(bucket_size(8_000_000), marks=pytest.mark.slow),
    ],
)
def test_classify_mergesort_compiles(one_chip, bucket):
    import jax

    from kart_tpu.ops.diff_kernel import _classify_mergesort_core

    ok, oo, oc = _block_shapes(bucket, one_chip)
    compiled = (
        jax.jit(_classify_mergesort_core).lower(ok, oo, ok, oo, oc, oc).compile()
    )
    assert _device_bytes(compiled) < 16e9


def _page_columns(rows, one_chip):
    """The four arrays a side of a chunk is cut from: its keys' page and
    the page after, its oids' page and the page after, ``rows`` rows each."""
    return [
        _shape(shape, dtype, one_chip)
        for shape, dtype in (((rows,), np.int64), ((rows, 5), np.uint32))
        for _ in range(2)
    ]


@pytest.mark.parametrize(
    "bucket",
    [
        1024,  # the minimum bucket: a revision of one page, the chunk all of it
        1152,  # the smallest bucket above it
        # a full chunk of the device route: what an overflowing chunk is
        # re-joined at on a TPU (the sort compiles for a minute)
        pytest.param(bucket_size(CLASSIFY_CHUNK_ROWS), marks=pytest.mark.slow),
    ],
)
def test_classify_split_entry_compiles(one_chip, bucket):
    """The sort-join's jitted entry — each side's chunk cut out of two
    pages a column on the device — under the name the benchmark's kernel
    metrics look for, and within the chip's memory with its inputs kept
    alive."""
    import jax

    from kart_tpu.ops.diff_kernel import _classify_split

    columns = _page_columns(bucket, one_chip)
    rows = _shape((4,), np.int32, one_chip)
    lowered = jax.jit(_classify_split.__wrapped__, static_argnames="sizes").lower(
        *columns, *columns, rows, sizes=(bucket, bucket)
    )
    assert "@jit__classify_mergesort_core_split" in lowered.as_text()
    assert _device_bytes(lowered.compile()) < 16e9


def _last_chunk_bucket(rows):
    """The bucket of the last key-range chunk of a ``rows``-row side."""
    return bucket_size(rows % CLASSIFY_CHUNK_ROWS)


_PAGE = bucket_size(CLASSIFY_CHUNK_ROWS)  # rows of a page of a long revision


@pytest.mark.parametrize(
    "pages,sizes",
    [
        # revisions of one page: the chunk is the page
        ((1024, 1024), (1024, 1024)),
        ((1152, 1152), (1152, 1152)),  # nine 128-row lines: not a whole tile, not a whole grid step
        # the production shapes are not marked slow: without the sort the
        # program compiles in ~6 s at any bucket. A full chunk of the device
        # route (every chunk but a call's last, PR 36) out of full pages:
        ((_PAGE, _PAGE), (_PAGE, _PAGE)),
        # the last chunk of a 10M-row call, both sides alike
        ((_PAGE, _PAGE), (_last_chunk_bucket(10_000_000),) * 2),
        # ... and after 0.5% of the keys were deleted all over the old range
        # and as many appended (the churn cell): the sides' buckets differ
        ((_PAGE, _PAGE), (_last_chunk_bucket(10_000_000), _last_chunk_bucket(10_050_000))),
        # the last chunk of the filtered cell's 2.94M survivors (PR 35)
        ((_PAGE, _PAGE), (_last_chunk_bucket(2_942_000),) * 2),
        # the last chunks of the merge cell's two diffs (PR 40): the 4M-row
        # ancestor against ours (50,000 appended), and against theirs, whose
        # 175,000 appended rows fill the chunk's bucket on that side
        ((_PAGE, _PAGE), (_last_chunk_bucket(4_000_000),) * 2),
        ((_PAGE, _PAGE), (_last_chunk_bucket(4_000_000), _PAGE)),
        # a revision of one small page against a long one: the chunk is
        # longer than the page it is cut from
        ((4608, _PAGE), (_PAGE, _PAGE)),
    ],
)
def test_classify_window_entry_compiles(one_chip, pages, sizes, monkeypatch):
    """The windowed join — what the device route runs a chunk on an
    accelerator — over a chunk cut out of pages at a dynamic row: Mosaic
    takes the Pallas kernel at the real widths (a slab block at a dynamic
    8-line offset, unaligned sublane loads, lane rotations and gathers),
    the program sorts nothing, keeps the name prefix the benchmark's kernel
    metrics look for, and fits the chip with its sixteen inputs kept alive
    (the overflow branch reuses them; resident pages outlive the call)."""
    import jax

    from kart_tpu.ops import diff_kernel
    from kart_tpu.ops.diff_kernel import _classify_window_split

    # the process's backend is the CPU, where the kernel would be traced
    # for the interpreter: here it is lowered for the described chip
    monkeypatch.setattr(diff_kernel, "_join_interpreted", lambda: False)
    lowered = jax.jit(
        _classify_window_split.__wrapped__, static_argnames="sizes"
    ).lower(
        *_page_columns(pages[0], one_chip), *_page_columns(pages[1], one_chip),
        _shape((4,), np.int32, one_chip), sizes=sizes,
    )
    text = lowered.as_text()
    assert "@jit__classify_mergesort_core_window_split" in text
    assert "stablehlo.sort" not in text
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not the interpreter
    assert _device_bytes(compiled) < 16e9


@pytest.mark.parametrize("column", [((), np.int64), ((5,), np.uint32)], ids=["keys", "oids"])
def test_resident_page_compiles_under_its_own_name(one_chip, column):
    """What makes a revision's last page whole on the device (the 10M-row
    cells': 562,816 rows put as a body view and a 32,768-row tail, padded
    out to a page): the chip's compiler takes it, and no
    ``jit__classify_*`` reader counts it."""
    import jax

    from kart_tpu.ops.blocks import bucket_body
    from kart_tpu.ops.diff_kernel import _resident_page

    width, dtype = column
    size = _last_chunk_bucket(10_000_000)
    body = bucket_body(size)
    lowered = jax.jit(_resident_page.__wrapped__, static_argnames="rows").lower(
        _shape((body,) + width, dtype, one_chip),
        _shape((size - body,) + width, dtype, one_chip),
        rows=_PAGE,
    )
    assert "jit__resident_page" in lowered.as_text()
    assert "jit__classify_" not in lowered.as_text()
    assert lowered.compile().output_shardings is not None


def test_clock_probe_compiles_under_its_own_name(one_chip):
    """The clock ping's program (``diff.device.clock``): the chip's compiler
    takes it, and the module is named so that no ``jit__classify_*`` reader
    counts it and the clock readers find it."""
    import jax

    from kart_tpu.ops.diff_kernel import _clock_probe

    lowered = jax.jit(_clock_probe.__wrapped__, out_shardings=one_chip).lower()
    assert "jit__clock_probe" in lowered.as_text()
    assert "jit__classify_" not in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("bucket", [1024, bucket_size(4_000_000)])
def test_merge_classify_compiles(one_chip, bucket):
    """The 3-way classify at a small bucket and at the 4M-row merge the
    chip smoke runs (searchsorted joins: seconds to compile at any size)."""
    import jax

    from kart_tpu.ops.merge_kernel import _merge_classify_padded_core

    side = _block_shapes(bucket, one_chip)
    union = (_shape((bucket,), np.int64, one_chip), side[2])
    jax.jit(_merge_classify_padded_core).lower(*side * 3, *union).compile()


@pytest.mark.parametrize("counts_only", [False, True], ids=["classes", "counts"])
@pytest.mark.parametrize(
    "batch_rows",
    [1024, pytest.param(DEVICE_BATCH_ROWS, marks=pytest.mark.slow)],
)
def test_record_batch_classify_compiles(mesh, batch_rows, counts_only):
    """The sharded backend's shard_map classify (the sort-join) over a one-
    and a four-device mesh."""
    from kart_tpu.diff.device_batch import make_batched_classify

    n = int(mesh.devices.size)
    sharded, _ = _sharded(mesh)
    side = _block_shapes(batch_rows, sharded, lead=(n,))
    fn = make_batched_classify(mesh, counts_only)
    # arg order: old keys, old oids, new keys, new oids, old count, new count
    fn.lower(side[0], side[1], side[0], side[1], side[2], side[2]).compile()


def test_sharded_merge_compiles(mesh):
    """`sharded_merge_classify`'s program at the 4M-row merge: block-cyclic
    shards of 4M/n rows each."""
    from kart_tpu.parallel.sharded_merge import make_sharded_merge

    n = int(mesh.devices.size)
    sharded, _ = _sharded(mesh)
    bucket = bucket_size(-(-4_000_000 // n), 256)
    keys, oids, _ = _block_shapes(bucket, sharded, lead=(n,))
    count = _shape((n,), np.int32, sharded)
    make_sharded_merge(mesh).lower(
        *(keys, oids, count) * 3, keys, count
    ).compile()


def test_sharded_bbox_compiles(mesh):
    """`sharded_envelope_hits` over a 10M-envelope sidecar block."""
    from kart_tpu.diff.backend import _make_sharded_bbox

    n = int(mesh.devices.size)
    sharded, replicated = _sharded(mesh)
    col = _shape((n, bucket_size(-(-10_000_000 // n))), np.float32, sharded)
    query = _shape((4,), np.float32, replicated)
    _make_sharded_bbox(mesh).lower(col, col, col, col, query).compile()


def test_sharded_mercator_compiles(mesh):
    """`sharded_merc_envelopes` at DEVICE_MIN_ENVELOPES rows: f64
    sin/log on a chip that emulates f64."""
    from kart_tpu.diff.backend import _make_sharded_merc
    from kart_tpu.routing import DEVICE_MIN_ENVELOPES

    n = int(mesh.devices.size)
    sharded, _ = _sharded(mesh)
    col = _shape(
        (n, bucket_size(-(-DEVICE_MIN_ENVELOPES // n))), np.float64, sharded
    )
    _make_sharded_merc(mesh).lower(col, col, col, col).compile()


def test_sharded_join_compiles(mesh):
    """`sharded_join_counts` at one query batch: 64 Ki probe rows against a
    4096-row build tile."""
    from kart_tpu.diff.backend import _make_sharded_join
    from kart_tpu.query.join import TILE_ROWS
    from kart_tpu.query.scan import DEFAULT_BATCH_ROWS

    n = int(mesh.devices.size)
    sharded, replicated = _sharded(mesh)
    probe = _shape(
        (n, bucket_size(-(-DEFAULT_BATCH_ROWS // n), minimum=256)),
        np.float32,
        sharded,
    )
    build = _shape((TILE_ROWS,), np.float32, replicated)
    _make_sharded_join(mesh).lower(*(probe,) * 4, *(build,) * 4).compile()


def test_sharded_refine_compiles(mesh):
    """`sharded_refine_pairs` at one exact-refine round of box polygons
    (4 segments, padded to the 8-segment bucket): int64 products."""
    from kart_tpu.diff.backend import _make_sharded_refine
    from kart_tpu.geom import DEFAULT_GEOM_BATCH_ROWS

    n = int(mesh.devices.size)
    sharded, _ = _sharded(mesh)
    per = bucket_size(-(-DEFAULT_GEOM_BATCH_ROWS // n), minimum=64)
    seg = _shape((n, per, 8), np.int32, sharded)
    n_seg = _shape((n, per), np.int32, sharded)
    is_poly = _shape((n, per), np.bool_, sharded)
    side = (seg,) * 4 + (n_seg,)
    _make_sharded_refine(mesh).lower(*side, *side, is_poly, is_poly).compile()
