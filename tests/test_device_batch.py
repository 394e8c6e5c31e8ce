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
from kart_tpu import telemetry
from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock
from kart_tpu.ops.diff_kernel import classify_blocks_host, classify_blocks_reference
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
    ks, os_, counts, copied = pack_round(keys, oids, splits, 0, 4, 128)
    assert copied == ks.nbytes + os_.nbytes + counts.nbytes  # 300 rows: ragged
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
        ks, os_, counts, _ = pack_round(keys, oids, splits, 0, 2, 256)
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


# --- a full round is views of the columns (ISSUE 33) -------------------------

def _mapped_columns(tmp_path, keys, oids, name="side"):
    """The two columns as a sidecar has them: sections of one read-only
    mapping that start at an odd byte (the header's length decides)."""
    n = len(keys)
    path = tmp_path / f"{name}.kcol"
    path.write_bytes(bytes(7) + keys.tobytes() + oids.tobytes())
    mm = np.memmap(path, dtype=np.uint8, mode="r")
    k = np.frombuffer(mm, dtype="<i8", count=n, offset=7)
    o = np.frombuffer(mm, dtype=np.uint8, count=20 * n, offset=7 + 8 * n)
    o = o.reshape(n, 5, 4).view(np.uint32).reshape(n, 5)
    assert not k.flags.aligned and not k.flags.writeable and not k.flags.owndata
    return k, o


def _assert_packed(ks, os_, counts, copied, keys, oids, splits, chunk0, n_shards, b):
    """Today's validity invariants of a copied round."""
    assert ks.flags.owndata and os_.flags.owndata
    assert not np.shares_memory(ks, keys) and not np.shares_memory(os_, oids)
    assert ks.shape == (n_shards, b) and os_.shape == (n_shards, b, 5)
    assert copied == ks.nbytes + os_.nbytes + counts.nbytes > 0
    n_chunks = len(splits) - 1
    for s in range(n_shards):
        c, m = chunk0 + s, int(counts[s])
        want = int(splits[c + 1] - splits[c]) if c < n_chunks else 0
        assert m == want
        assert np.all(ks[s, m:] == PAD_KEY) and not np.any(os_[s, m:])
        if m:
            lo = int(splits[c])
            np.testing.assert_array_equal(ks[s, :m], keys[lo : lo + m])
            np.testing.assert_array_equal(os_[s, :m], oids[lo : lo + m])


def _assert_views(ks, os_, counts, copied, keys, oids, splits, chunk0, n_shards, b):
    assert copied == 0
    assert not ks.flags.owndata and not os_.flags.owndata
    assert np.shares_memory(ks, keys) and np.shares_memory(os_, oids)
    assert ks.shape == (n_shards, b) and ks.dtype == np.int64
    assert os_.shape == (n_shards, b, 5) and os_.dtype == np.uint32
    assert counts.tolist() == [b] * n_shards and counts.dtype == np.int64
    lo = int(splits[chunk0])
    np.testing.assert_array_equal(ks.reshape(-1), keys[lo : lo + n_shards * b])
    np.testing.assert_array_equal(os_.reshape(-1, 5), oids[lo : lo + n_shards * b])


@pytest.mark.parametrize(
    "case",
    ["full_round", "ragged_last_round", "short_chunk", "beyond_the_plan",
     "int32_keys", "strided_keys", "strided_oids", "one_shard"],
)
def test_pack_round_hands_a_full_round_over_as_views(case, tmp_path):
    """A round whose every shard slot is full is the column itself,
    reshaped: nothing allocated, filled or copied — also when the column is
    an unaligned read-only mapping, as a sidecar's are. Any other round, and
    any column the device program could not read as it lies, is packed into
    fresh padded arrays as before."""
    rng = np.random.default_rng(33)
    b, n_shards = 64, 1 if case == "one_shard" else 4
    n = 2 * n_shards * b + 37  # two full rounds and a ragged third
    keys, oids = _random_keys_oids(rng, n)
    keys, oids = _mapped_columns(tmp_path, keys, oids)
    (splits,), n_chunks = batch_splits((keys,), b)
    assert n_chunks == 2 * n_shards + 1
    chunk0, views = n_shards, True  # the second round
    if case == "ragged_last_round":
        chunk0, views = 2 * n_shards, False
    elif case == "short_chunk":
        # a side whose key-aligned chunks come short (the other side had
        # more keys under the boundary): one row fewer in one slot
        splits = splits.copy()
        splits[chunk0 + 2 :] -= 1
        views = False
    elif case == "beyond_the_plan":
        chunk0, views = 3 * n_shards, False
    elif case == "int32_keys":
        keys, views = keys.astype(np.int32), False
    elif case == "strided_keys":
        keys, views = np.repeat(keys, 2)[::2], False
        assert not keys.flags.c_contiguous
    elif case == "strided_oids":
        oids, views = np.repeat(oids, 2, axis=1)[:, ::2], False
        assert not oids.flags.c_contiguous
    got = pack_round(keys, oids, splits, chunk0, n_shards, b)
    check = _assert_views if views else _assert_packed
    check(*got, keys, oids, splits, chunk0, n_shards, b)


def test_pack_round_decides_for_each_side_alone(tmp_path):
    """Deletes on one side only: the old side's chunks stay full (views),
    the new side's come short under the same key boundaries (copied)."""
    rng = np.random.default_rng(34)
    b, n_shards = 64, 4
    keys, oids = _random_keys_oids(rng, 3 * n_shards * b)
    keep = np.ones(len(keys), dtype=bool)
    keep[5::17] = False
    old = _mapped_columns(tmp_path, keys, oids, "old")
    new = _mapped_columns(tmp_path, keys[keep], oids[keep], "new")
    (old_splits, new_splits), _ = batch_splits((old[0], new[0]), b)
    got_old = pack_round(*old, old_splits, 0, n_shards, b)
    got_new = pack_round(*new, new_splits, 0, n_shards, b)
    _assert_views(*got_old, *old, old_splits, 0, n_shards, b)
    _assert_packed(*got_new, *new, new_splits, 0, n_shards, b)


def _view_case(name, rng, tmp_path, b, n_shards):
    """(old, new) blocks over mapped columns for one pattern of full and
    short rounds, ``b`` rows a shard."""
    per_round = n_shards * b
    if name == "every_round_full":
        keys, oids = _random_keys_oids(rng, 3 * per_round)
        new_keys, new_oids = keys, oids.copy()
        new_oids[7::97, 1] ^= 1
    elif name == "ragged_last_round":
        keys, oids = _random_keys_oids(rng, 2 * per_round + b + 19)
        new_keys, new_oids = keys, oids.copy()
        new_oids[3::89, 0] ^= 1
    elif name == "deletes_and_appended_inserts":
        # uniform deletes + inserts appended past the old side's range:
        # which side is full differs round by round
        keys, oids = _random_keys_oids(rng, 4 * per_round)
        keep = np.ones(len(keys), dtype=bool)
        keep[rng.choice(len(keys), size=len(keys) // 50, replace=False)] = False
        ins = np.arange(keys[-1] + 1, keys[-1] + 1 + per_round + 11, dtype=np.int64)
        new_keys = np.concatenate([keys[keep], ins])
        new_oids = np.concatenate(
            [oids[keep], rng.integers(0, 2**32, size=(len(ins), 5), dtype=np.uint32)]
        )
        new_oids[11::101, 2] ^= 1
    elif name == "old_side_empty":
        new_keys, new_oids = _random_keys_oids(rng, 2 * per_round)
        keys, oids = np.zeros(0, dtype=np.int64), np.zeros((0, 5), dtype=np.uint32)
    else:
        raise AssertionError(name)
    old_cols = _mapped_columns(tmp_path, keys, oids, "old") if len(keys) else (keys, oids)
    new_cols = _mapped_columns(tmp_path, new_keys, new_oids, "new")
    return (
        FeatureBlock(*old_cols, None, len(keys)),
        FeatureBlock(*new_cols, None, len(new_keys)),
    )


def _recount(old, new, b, n_shards):
    """What the spans must say, from `batch_splits`' output alone:
    per round (view sides, bytes copied, bytes put), view rounds."""
    (so, sn), n_chunks = batch_splits(
        (old.keys[: old.count], new.keys[: new.count]), b
    )
    side_bytes = n_shards * b * 28 + n_shards * 8
    rounds, counts_put = [], False
    for r in range(max(-(-n_chunks // n_shards), 1)):
        c0, sides, put = r * n_shards, 0, 0
        for splits in (so, sn):
            full = c0 + n_shards <= n_chunks and np.all(
                np.diff(splits[c0 : c0 + n_shards + 1]) == b
            )
            sides += bool(full)
            put += side_bytes
            if full and counts_put:
                put -= n_shards * 8  # the full count vector is on the mesh
            counts_put = counts_put or bool(full)
        rounds.append((sides, (2 - sides) * side_bytes, put))
    return rounds, sum(s == 2 for s, _, _ in rounds)


VIEW_CASES = [
    "every_round_full", "ragged_last_round", "deletes_and_appended_inserts",
    "old_side_empty",
]


@pytest.mark.parametrize("counts_only", [False, True], ids=["classes", "counts"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("case", VIEW_CASES)
def test_rounds_of_views_classify_like_the_plain_reference(
    case, n_shards, counts_only, tmp_path
):
    """`classify_blocks_batched` over mapped columns, rounds of views and
    copied rounds mixed (and sides mixed within a round), against
    `classify_blocks_reference`, bit for bit; and its spans and counter say
    how often the views engaged: a recount from `batch_splits`' output."""
    from kart_tpu.ops.diff_kernel import DELETE, INSERT, UPDATE

    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    b = 128
    old, new = _view_case(case, np.random.default_rng(35), tmp_path, b, n_shards)
    want_old, want_new = classify_blocks_reference(old, new)
    want_counts = {
        "inserts": int(np.sum(want_new == INSERT)),
        "updates": int(np.sum(want_new == UPDATE)),
        "deletes": int(np.sum(want_old == DELETE)),
    }
    assert sum(want_counts.values()) > 0
    want_rounds, want_view_rounds = _recount(old, new, b, n_shards)
    sides = [s for s, _, _ in want_rounds]
    if case == "every_round_full":
        assert sides == [2, 2, 2]
    elif case == "ragged_last_round":
        assert set(sides[:-1]) == {2} and sides[-1] == 0
    elif case == "deletes_and_appended_inserts":
        assert {0, 1} <= set(sides)  # a round of one view side and one copied
    else:
        assert sides == [1, 1]

    telemetry.reset()
    telemetry.enable(metrics=True, trace=True)
    try:
        got_old, got_new, got_counts = classify_blocks_batched(
            old, new, mesh=make_mesh(n_shards), batch_rows=b, counts_only=counts_only
        )
        counters = {name: v for name, _, v in telemetry.snapshot()["counters"]}
        events = telemetry.drain_events()
    finally:
        telemetry.reset()
    assert got_counts == want_counts
    if counts_only:
        assert got_old is None and got_new is None
    else:
        np.testing.assert_array_equal(got_old, want_old)
        np.testing.assert_array_equal(got_new, want_new)

    spans = {}
    for e in events:
        spans.setdefault(e["name"], []).append(e["args"])
    (root,) = spans["diff.device.classify"]
    assert root["rounds"] == len(want_rounds)
    assert root["view_rounds"] == want_view_rounds
    assert counters.get("diff.device.view_rounds", 0) == want_view_rounds
    assert [(p["view_sides"], p["bytes"]) for p in spans["diff.device.pack"]] == [
        (s, copied) for s, copied, _ in want_rounds
    ]
    assert [t["bytes"] for t in spans["diff.device.transfer"]] == [
        put for _, _, put in want_rounds
    ]
    assert root["bytes"] == counters["diff.device.h2d_bytes"] == sum(
        put for _, _, put in want_rounds
    )


@pytest.mark.parametrize("case", VIEW_CASES)
def test_every_host_to_device_byte_goes_through_the_transfer_span(case, tmp_path):
    """The jitted call is handed device arrays and moves nothing itself:
    with implicit host→device transfers disallowed (an explicit
    ``device_put`` is not one) the classify still runs, rounds of views and
    copied rounds alike — so ``diff.device.transfer`` times, and its
    ``bytes`` count, everything that crosses. (A round's host arrays handed
    to the call instead are transferred a second time inside
    ``diff.device.kernel``: the answer is the same and only the chip's clock
    shows it — PERF.md §6, PR 33.)"""
    n_shards = min(jax.device_count(), 4)
    old, new = _view_case(case, np.random.default_rng(36), tmp_path, 128, n_shards)
    want_old, want_new = classify_blocks_reference(old, new)
    with jax.transfer_guard_host_to_device("disallow"):
        got_old, got_new, _ = classify_blocks_batched(
            old, new, mesh=make_mesh(n_shards), batch_rows=128
        )
    np.testing.assert_array_equal(got_old, want_old)
    np.testing.assert_array_equal(got_new, want_new)
