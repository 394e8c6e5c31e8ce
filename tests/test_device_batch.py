"""Device record-batch layout (ISSUE 6): block -> padded fixed-shape
batches -> block must round-trip exactly, and the sharded batched classify
must be bit-identical to host_native across attr/geom/delete/insert mixes
and every mesh size the virtual 8-device platform offers."""

import numpy as np
import pytest

import jax

from kart_tpu.diff.device_batch import (
    DEVICE_BATCH_ROWS,
    batch_splits,
    classify_blocks_batched,
    pack_round,
    roundtrip_arrays,
)
from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock
from kart_tpu.ops.diff_kernel import classify_blocks_host
from kart_tpu.parallel.mesh import make_mesh


def _random_keys_oids(rng, n, key_space=None):
    key_space = key_space or max(10 * n, 10)
    keys = np.sort(rng.choice(key_space, size=n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    return keys, oids


def _edited_pair(rng, n, n_ins, n_upd, n_del):
    """(old, new) FeatureBlocks with a known insert/update/delete mix —
    geometry edits are oid edits at this layer, same as attribute edits."""
    keys, oids = _random_keys_oids(rng, n)
    old = FeatureBlock.from_arrays(keys.copy(), oids.copy(), [f"f/{k}" for k in keys])
    keep = np.setdiff1d(np.arange(n), rng.choice(n, size=n_del, replace=False))
    nk, no = keys[keep], oids[keep].copy()
    if n_upd:
        up = rng.choice(len(nk), size=n_upd, replace=False)
        no[up] = rng.integers(0, 2**32, size=(n_upd, 5), dtype=np.uint32)
    ik = np.arange(10 * n, 10 * n + n_ins, dtype=np.int64)
    io = rng.integers(0, 2**32, size=(n_ins, 5), dtype=np.uint32)
    new = FeatureBlock.from_arrays(
        np.concatenate([nk, ik]),
        np.concatenate([no, io]),
        [f"f/{k}" for k in np.concatenate([nk, ik])],
    )
    return old, new


# --- round-trip properties ---------------------------------------------------

@pytest.mark.parametrize(
    "n,batch_rows,n_shards",
    [
        (0, 64, 1),        # empty block
        (1, 64, 1),        # single row
        (63, 64, 1),       # under one batch
        (64, 64, 1),       # exactly one batch
        (65, 64, 1),       # ragged last batch
        (1000, 64, 4),     # many rounds, multi-shard
        (12345, 1000, 8),  # ragged everything
    ],
)
def test_block_batches_block_roundtrip_exact(n, batch_rows, n_shards):
    rng = np.random.default_rng(n + batch_rows)
    keys, oids = _random_keys_oids(rng, n, key_space=max(50 * n, 10))
    out_keys, out_oids = roundtrip_arrays(keys, oids, batch_rows, n_shards)
    np.testing.assert_array_equal(out_keys, keys)
    np.testing.assert_array_equal(out_oids, oids)


def test_roundtrip_property_random():
    rng = np.random.default_rng(7)
    for _ in range(25):
        n = int(rng.integers(0, 5000))
        batch_rows = int(rng.integers(1, 700))
        n_shards = int(rng.choice([1, 2, 3, 8]))
        keys, oids = _random_keys_oids(rng, n, key_space=max(4 * n, 10))
        out_keys, out_oids = roundtrip_arrays(keys, oids, batch_rows, n_shards)
        np.testing.assert_array_equal(out_keys, keys)
        np.testing.assert_array_equal(out_oids, oids)


def test_batch_splits_capacity_and_alignment():
    """Every chunk <= batch_rows on EVERY side; boundaries are key values
    (a shared key lands in the same chunk of both sides); coverage exact."""
    rng = np.random.default_rng(11)
    a = np.sort(rng.choice(100_000, size=9000, replace=False)).astype(np.int64)
    b = np.sort(rng.choice(100_000, size=4000, replace=False)).astype(np.int64)
    batch_rows = 512
    (sa, sb), n_chunks = batch_splits((a, b), batch_rows)
    assert sa[0] == 0 and sb[0] == 0
    assert sa[-1] == len(a) and sb[-1] == len(b)
    assert np.all(np.diff(sa) >= 0) and np.all(np.diff(sb) >= 0)
    assert np.all(np.diff(sa) <= batch_rows)
    assert np.all(np.diff(sb) <= batch_rows)
    # alignment: for every chunk, the key ranges of the two sides overlap
    # only within the chunk — max key of chunk c on one side is below the
    # min key of chunk c+1 on the other
    for c in range(n_chunks - 1):
        hi_a = a[sa[c + 1] - 1] if sa[c + 1] > sa[c] else None
        lo_b_next = b[sb[c + 1]] if sb[c + 1] < len(b) else None
        if hi_a is not None and lo_b_next is not None:
            assert hi_a < lo_b_next
        hi_b = b[sb[c + 1] - 1] if sb[c + 1] > sb[c] else None
        lo_a_next = a[sa[c + 1]] if sa[c + 1] < len(a) else None
        if hi_b is not None and lo_a_next is not None:
            assert hi_b < lo_a_next


def test_batch_splits_takes_unaligned_sides_without_copying_them():
    """A sidecar's mmap'd key section is a read-only view that starts at an
    odd byte: the boundaries are those of aligned copies, and no boundary
    search is handed a whole side (np.searchsorted would copy it first —
    what made `batch_splits` 78% of a four-chip count at 10M rows)."""
    rng = np.random.default_rng(13)
    a = np.sort(rng.choice(400_000, size=30_000, replace=False)).astype(np.int64)
    b = np.sort(rng.choice(400_000, size=21_000, replace=False)).astype(np.int64)

    def unaligned(keys):
        raw = bytes(7) + keys.tobytes()
        view = np.frombuffer(raw, dtype=np.int64, count=len(keys), offset=7)
        assert not view.flags.aligned and not view.flags.writeable
        return view

    want, want_chunks = batch_splits((a, b), 1000)
    got, got_chunks = batch_splits((unaligned(a), unaligned(b)), 1000)
    assert got_chunks == want_chunks >= 30
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    # and those of the plain way: every boundary searched over the whole side
    los, plain = [0, 0], [[0], [0]]
    while any(lo < len(k) for lo, k in zip(los, (a, b))):
        cands = [k[lo + 1000] for lo, k in zip(los, (a, b)) if lo + 1000 < len(k)]
        for i, k in enumerate((a, b)):
            los[i] = int(np.searchsorted(k, min(cands))) if cands else len(k)
            plain[i].append(los[i])
    for w, p in zip(want, plain):
        np.testing.assert_array_equal(w, p)


def test_batch_splits_disjoint_key_ranges():
    """Totally disjoint key ranges (renumbered-pk revision): one side's
    chunks go empty rather than overflowing the other's."""
    a = np.arange(0, 1000, dtype=np.int64)
    b = np.arange(50_000, 51_000, dtype=np.int64)
    (sa, sb), n_chunks = batch_splits((a, b), 100)
    assert np.all(np.diff(sa) <= 100) and np.all(np.diff(sb) <= 100)
    assert sa[-1] == len(a) and sb[-1] == len(b)


def test_pack_round_validity_masks():
    """Padding discipline: everything past the validity count is PAD_KEY /
    zero, real rows are bit-exact, shapes are fixed regardless of data."""
    rng = np.random.default_rng(3)
    keys, oids = _random_keys_oids(rng, 300)
    (splits,), n_chunks = batch_splits((keys,), 128)
    ks, os_, counts = pack_round(keys, oids, splits, 0, 4, 128)
    assert ks.shape == (4, 128) and os_.shape == (4, 128, 5)
    for s in range(4):
        c = int(counts[s])
        assert np.all(ks[s, c:] == PAD_KEY)
        assert not np.any(os_[s, c:])
        if s < n_chunks:
            lo, hi = int(splits[s]), int(splits[s + 1])
            np.testing.assert_array_equal(ks[s, :c], keys[lo:hi])
            np.testing.assert_array_equal(os_[s, :c], oids[lo:hi])


def test_fixed_shapes_across_blocks():
    """The whole point of pad-to-batch-size: two different datasets/commits
    produce identically-shaped rounds, so XLA compiles once."""
    rng = np.random.default_rng(9)
    shapes = set()
    for n in (100, 999, 4567):
        keys, oids = _random_keys_oids(rng, n)
        (splits,), _ = batch_splits((keys,), 256)
        ks, os_, counts = pack_round(keys, oids, splits, 0, 2, 256)
        shapes.add((ks.shape, os_.shape, counts.shape))
    assert len(shapes) == 1


# --- classify parity ---------------------------------------------------------

MIXES = [
    dict(n=3000, n_ins=0, n_upd=97, n_del=0),    # attr/geom-only edits
    dict(n=3000, n_ins=113, n_upd=0, n_del=0),   # inserts only
    dict(n=3000, n_ins=0, n_upd=0, n_del=131),   # deletes only
    dict(n=5000, n_ins=41, n_upd=77, n_del=53),  # everything at once
]


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])  # 4: the mesh cell's
def test_batched_classify_bit_identical_to_host_native(mix, n_shards):
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    rng = np.random.default_rng(sum(mix.values()))
    old, new = _edited_pair(rng, **mix)
    want_old, want_new, want_counts = classify_blocks_host(old, new)
    got_old, got_new, got_counts = classify_blocks_batched(
        old, new, mesh=make_mesh(n_shards), batch_rows=512
    )
    assert got_counts == want_counts
    np.testing.assert_array_equal(got_old, want_old)
    np.testing.assert_array_equal(got_new, want_new)


def test_shard_kernel_agrees_with_host():
    rng = np.random.default_rng(17)
    old, new = _edited_pair(rng, n=2000, n_ins=19, n_upd=23, n_del=29)
    want = classify_blocks_host(old, new)
    got = classify_blocks_batched(
        old, new, mesh=make_mesh(min(jax.device_count(), 4)),
        batch_rows=256,
    )
    assert got[2] == want[2]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_batched_classify_empty_sides():
    empty = FeatureBlock.from_arrays(
        np.zeros(0, dtype=np.int64), np.zeros((0, 5), dtype=np.uint32), []
    )
    rng = np.random.default_rng(1)
    _, new = _edited_pair(rng, n=500, n_ins=7, n_upd=11, n_del=13)
    mesh = make_mesh(min(jax.device_count(), 2))
    for a, b in ((empty, new), (new, empty), (empty, empty)):
        want = classify_blocks_host(a, b)
        got = classify_blocks_batched(a, b, mesh=mesh, batch_rows=128)
        assert got[2] == want[2]
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


def test_default_batch_rows_sane():
    assert DEVICE_BATCH_ROWS >= 1


def test_counts_only_matches_full_classify():
    """The `-o feature-count` path: counts_only rounds must psum to exactly
    the full classify's counts with no class arrays materialised."""
    rng = np.random.default_rng(21)
    old, new = _edited_pair(rng, n=5000, n_ins=41, n_upd=77, n_del=53)
    want = classify_blocks_host(old, new)[2]
    mesh = make_mesh(min(jax.device_count(), 4))
    got_old, got_new, got = classify_blocks_batched(
        old, new, mesh=mesh, batch_rows=512, counts_only=True
    )
    assert got_old is None and got_new is None
    assert got == want


# --- the four-device mesh against the plain reference (ISSUE 28) -------------

def _block(keys, oids):
    return FeatureBlock.from_arrays(keys, oids, [f"f/{k}" for k in keys])


def _mesh_case(name, rng):
    """(old, new) for one kind of edit; every case spans several rounds of a
    four-shard mesh at 256 rows a shard."""
    if name == "renumbered":
        # the same features under a disjoint key range: all deletes + inserts
        keys, oids = _random_keys_oids(rng, 3000)
        return _block(keys, oids), _block(keys + 10**9, oids.copy())
    if name in ("old_empty", "new_empty"):
        keys, oids = _random_keys_oids(rng, 3000)
        empty = _block(np.zeros(0, dtype=np.int64), np.zeros((0, 5), dtype=np.uint32))
        full = _block(keys, oids)
        return (empty, full) if name == "old_empty" else (full, empty)
    mix = {
        "inserts": dict(n_ins=113, n_upd=0, n_del=0),
        "updates": dict(n_ins=0, n_upd=97, n_del=0),
        "deletes": dict(n_ins=0, n_upd=0, n_del=131),
        "mixed": dict(n_ins=41, n_upd=77, n_del=53),
    }[name]
    return _edited_pair(rng, n=4000, **mix)


@pytest.mark.parametrize("counts_only", [False, True], ids=["classes", "counts"])
@pytest.mark.parametrize(
    "case",
    ["inserts", "updates", "deletes", "mixed", "renumbered", "old_empty", "new_empty"],
)
def test_four_device_mesh_equals_the_plain_reference(case, counts_only):
    """`classify_blocks_batched` over a four-device mesh against
    `classify_blocks_reference` (numpy, no kernels, no batching): the same
    classes in block-row order and the same counts; with ``counts_only`` the
    counts alone, and no class array at all."""
    from kart_tpu.ops.diff_kernel import (
        DELETE,
        INSERT,
        UPDATE,
        classify_blocks_reference,
    )

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    rng = np.random.default_rng(28)
    old, new = _mesh_case(case, rng)
    want_old, want_new = classify_blocks_reference(old, new)
    want_counts = {
        "inserts": int(np.sum(want_new == INSERT)),
        "updates": int(np.sum(want_new == UPDATE)),
        "deletes": int(np.sum(want_old == DELETE)),
    }
    assert sum(want_counts.values()) > 0
    got_old, got_new, got_counts = classify_blocks_batched(
        old, new, mesh=make_mesh(4), batch_rows=256, counts_only=counts_only
    )
    assert got_counts == want_counts
    if counts_only:
        assert got_old is None and got_new is None
    else:
        np.testing.assert_array_equal(got_old, want_old)
        np.testing.assert_array_equal(got_new, want_new)
