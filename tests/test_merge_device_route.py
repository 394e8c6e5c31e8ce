"""The merge classify: ``kart diff``'s classify twice through the diff's
backend and the three-way rule over the changed keys
(``ops/merge_kernel.py merge_classify_two_diffs``), on the host engine, on
one device over resident pages (forced onto XLA-CPU as
``tests/test_resident_pages.py`` forces the diff's route) and on the
suite's virtual CPU mesh, against the dict-per-key oracle
``merge_classify_reference``; and a ``kart merge`` of a small repository on
every route: the device, the host engine, and blocks from a walk of the
feature trees as before the merge read sidecars."""

import importlib.util
import os

import numpy as np
import pytest

from kart_tpu import telemetry as tm
from kart_tpu.diff.backend import merge_classify
from kart_tpu.ops import diff_kernel, resident
from kart_tpu.ops.blocks import FeatureBlock
from kart_tpu.ops.merge_kernel import (
    CONFLICT,
    KEEP_OURS,
    TAKE_THEIRS,
    merge_classify_reference,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHUNK = 10_240  # on the bucket grid, as tests/test_resident_pages.py's


@pytest.fixture
def device(monkeypatch):
    """The one-device route forced, the mesh closed, a page store and a
    telemetry registry of this test's own."""
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 30))
    tm.reset()
    tm.enable(metrics=True, trace=True)
    yield
    tm.reset()


def _revision(rows, name=None):
    """{key: first oid word} -> a block as ``sidecar.load_block`` hands it
    over: sorted, unpadded, named after its tree where ``name`` is given."""
    keys = np.array(sorted(rows), dtype=np.int64)
    oids = np.zeros((len(keys), 5), dtype=np.uint32)
    oids[:, 0] = [rows[k] for k in keys.tolist()]
    oids[:, 4] = 7
    return FeatureBlock(keys, oids, None, len(keys), tree_oid=name and name * 40)


def _expect(blocks, got):
    """An engine's answer against the oracle."""
    union, decision, presence, stats = got
    ref_union, ref_decision, ref_presence = merge_classify_reference(*blocks)
    np.testing.assert_array_equal(union, ref_union)
    np.testing.assert_array_equal(decision, ref_decision)
    np.testing.assert_array_equal(presence, ref_presence)
    assert decision.dtype == np.int8 and presence.dtype == np.int8
    assert union.dtype == np.int64
    assert stats == {
        "conflicts": int(np.sum(ref_decision == CONFLICT)),
        "take_theirs": int(np.sum(ref_decision == TAKE_THEIRS)),
    }


# -- every presence pattern ---------------------------------------------------

#: name -> (ancestor, ours, theirs) values of key 500 (None = absent), and
#: what the rule says of it
PATTERNS = {
    "untouched": ((1, 1, 1), KEEP_OURS),
    "edit_edit_same": ((1, 2, 2), KEEP_OURS),
    "edit_edit_different": ((1, 2, 3), CONFLICT),
    "edit_delete": ((1, 2, None), CONFLICT),
    "delete_edit": ((1, None, 3), CONFLICT),
    "delete_delete": ((1, None, None), KEEP_OURS),
    "add_add_same": ((None, 2, 2), KEEP_OURS),
    "add_add_different": ((None, 2, 3), CONFLICT),
    "ours_edits": ((1, 2, 1), KEEP_OURS),
    "theirs_edits": ((1, 1, 3), TAKE_THEIRS),
    "ours_deletes": ((1, None, 1), KEEP_OURS),
    "theirs_deletes": ((1, 1, None), TAKE_THEIRS),
    "ours_adds": ((None, 2, None), KEEP_OURS),
    "theirs_adds": ((None, None, 3), TAKE_THEIRS),
}


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_a_presence_pattern_on_the_device_route(pattern, device):
    """One key in the pattern, among keys nobody touched and keys each side
    changed alone: its decision and presence are the rule's."""
    values, decision = PATTERNS[pattern]
    versions = [{k: 1 for k in range(0, 3000, 3)} for _ in range(3)]
    for k in range(30, 3000, 60):
        versions[1][k] = 5  # ours alone
    for k in range(63, 3000, 90):
        versions[2][k] = 6  # theirs alone
    for version, value in zip(versions, values):
        version.pop(500, None)
        if value is not None:
            version[500] = value
    blocks = [_revision(v) for v in versions]
    got = merge_classify(*blocks)
    _expect(blocks, got)
    union, got_decision, presence, _ = got
    absent = values == (1, None, None)  # still a key of the union: the ancestor's
    at = int(np.searchsorted(union, 500))
    assert union[at] == 500 and got_decision[at] == decision
    assert presence[at] == sum(
        bit for bit, value in zip((1, 2, 4), values) if value is not None
    )
    assert absent == (presence[at] == 1)


# -- shapes of a merge ---------------------------------------------------------

def _random_merge(n, seed, stride=3):
    """Three revisions of ``n`` keys with every kind of change somewhere."""
    rng = np.random.default_rng(seed)
    ancestor = {int(k) * stride + 5: 1 for k in range(n)}
    sides = []
    for salt in (2, 3):
        side = dict(ancestor)
        keys = np.array(sorted(ancestor))
        for k in rng.choice(keys, n // 7, replace=False).tolist():
            side[k] = int(rng.integers(2, 4))  # some edits coincide
        for k in rng.choice(keys, n // 20, replace=False).tolist():
            side.pop(k, None)
        for k in (rng.choice(keys[:-1], n // 25, replace=False) + 1).tolist():
            side[k] = salt if k % 2 else 9  # some inserts coincide
        sides.append(side)
    return ancestor, *sides


def _hole(version, lo, hi):
    return {k: v for i, (k, v) in enumerate(sorted(version.items())) if not lo <= i < hi}


SHAPES = {
    "several_chunks": lambda: _random_merge(26_000, 1),
    "one_chunk": lambda: _random_merge(4_000, 2),
    # 800 consecutive rows gone on one side: on an accelerator the window of
    # that chunk overflows and the sort-join answers it
    "hole_in_theirs": lambda: (
        (m := _random_merge(24_000, 3))[0], m[1], _hole(m[2], 12_000, 12_800)
    ),
    "hole_in_ours": lambda: (
        (m := _random_merge(24_000, 4))[0], _hole(m[1], 3_000, 3_800), m[2]
    ),
    "ours_empty": lambda: ((m := _random_merge(3_000, 5))[0], {}, m[2]),
    "theirs_empty": lambda: ((m := _random_merge(3_000, 6))[0], m[1], {}),
    # the dataset was added on both branches: no ancestor
    "no_ancestor": lambda: ({}, *(_random_merge(3_000, 7)[1:])),
    "only_theirs_has_it": lambda: ({}, {}, _random_merge(3_000, 8)[2]),
    "nobody_has_it": lambda: ({}, {}, {}),
    # a renumbering: ours drops the low keys and adds a range above
    "disjoint_ranges": lambda: (
        {k: 1 for k in range(2000)}, {k: 1 for k in range(1000, 4000)},
        {k: 1 for k in range(2000)},
    ),
}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("backend", ["host_native", "device_jax", "sharded_jax"])
def test_the_device_route_equals_the_reference(backend, shape, device, monkeypatch):
    """Each engine the diff's ladder can pick answers the merge as the
    oracle does, its two diffs under ``diff.classify`` spans that name it:
    the host engine, one device over chunks of resident pages, and the
    suite's virtual mesh over record batches of 2,048 rows a shard."""
    from kart_tpu.diff import device_batch
    from kart_tpu.parallel.sharded_diff import STATS

    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setattr(device_batch, "DEVICE_BATCH_ROWS", 2_048)
    monkeypatch.setenv("KART_DIFF_BACKEND", backend)
    blocks = [_revision(v, name) for v, name in zip(SHAPES[shape](), "aot")]
    mesh_calls = STATS["sharded_classify_calls"]
    got = merge_classify(*blocks)
    _expect(blocks, got)
    diffs = sum(bool(max(blocks[0].count, b.count)) for b in blocks[1:])
    assert STATS["sharded_classify_calls"] - mesh_calls == (
        diffs if backend == "sharded_jax" else 0
    )
    events = tm.drain_events()
    (merge,) = [e["args"] for e in events if e["name"] == "diff.merge_classify"]
    assert merge["backend"] == backend
    assert (merge["rows_ancestor"], merge["rows_ours"], merge["rows_theirs"]) == tuple(
        b.count for b in blocks
    )
    assert merge["union"] == len(got[0])
    assert (merge["conflicts"], merge["take_theirs"]) == (
        got[3]["conflicts"], got[3]["take_theirs"]
    )
    sides = [e["args"] for e in events if e["name"] == "diff.classify"]
    assert [s["side"] for s in sides] == ["ours", "theirs"]
    assert all(s["backend"] == backend and not s["counts_only"] for s in sides)
    (combine,) = [e["args"] for e in events if e["name"] == "merge.combine"]
    changed = got[1] != KEEP_OURS
    assert combine["both"] >= int(np.sum(got[1] == CONFLICT))
    assert combine["changed_theirs"] >= int(np.sum(changed))
    assert not [k for k in tm.counters_snapshot() if k[0] == "diff.device.fallbacks"]


@pytest.mark.parametrize("shape", ["several_chunks", "hole_in_theirs"])
def test_the_windowed_join_answers_the_merge_as_on_a_tpu(shape, device, monkeypatch):
    """The route as a TPU takes it (the backend's name forced, the Pallas
    kernel interpreted): a chunk whose window overflows on one side is
    answered by the sort-join, and the merge's answer is the reference's."""
    from kart_tpu import runtime

    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    blocks = [_revision(v, name) for v, name in zip(SHAPES[shape](), "aot")]
    _expect(blocks, merge_classify(*blocks))
    overflows = tm.counters_snapshot().get(("diff.device.join_overflows", ()), 0)
    assert (overflows > 0) == (shape == "hole_in_theirs")
    kernels = [e["args"] for e in tm.drain_events() if e["name"] == "diff.device.kernel"]
    assert {k["join"] for k in kernels} == (
        {"window", "sort"} if overflows else {"window"}
    )


def test_the_second_merge_finds_every_page_and_the_ancestors_are_read_once(device, monkeypatch):
    """The ancestor's pages serve both diffs; a merge repeated ships nothing,
    and ``diff.classify`` says so as it does for a diff."""
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    blocks = [_revision(v, name) for v, name in zip(_random_merge(26_000, 9), "aot")]

    def shares():
        merge_classify(*blocks)
        return [
            (e["args"]["side"], e["args"]["resident_bytes"], e["args"]["input_bytes"])
            for e in tm.drain_events() if e["name"] == "diff.classify"
        ]

    (_, ours_hit, ours_read), (_, theirs_hit, theirs_read) = shares()
    assert ours_hit == 0 and ours_read > 0
    assert theirs_hit == blocks[0].count * 28  # the ancestor's rows, put by the first diff
    assert all(hit == read for _, hit, read in shares())


# -- the merged feature tree, named before it is written ----------------------

@pytest.mark.parametrize(
    "n, first",
    [(1, 5), (63, 0), (64, 0), (65, 1), (5_000, 1 << 24), (40_000, 100)],
    ids=["one_row", "under_a_leaf", "a_leaf", "over_a_leaf", "serial_ids", "sparse_ids"],
)
def test_the_merged_tree_is_the_plans_tree_and_is_written_once(n, first, tmp_path, monkeypatch):
    """``write_int_feature_tree``: sorted columns through the native leaf
    stream in several batches, unsorted ones through the plan, both the tree
    ``build_int_feature_tree`` builds; a second call finds the root and
    writes no pack."""
    from kart_tpu import native
    from kart_tpu.core import feature_tree
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.models.paths import PathEncoder

    rng = np.random.default_rng(n)
    pks = first + np.sort(rng.choice(n * 3, n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 256, (n, 20), dtype=np.uint8)
    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1_000)

    def whole():
        yield pks, oids

    def in_parts():  # uneven batches, as a merge's are
        for lo in range(0, n, 1_700):
            yield pks[lo : lo + 1_700], oids[lo : lo + 1_700]

    if native.load_io() is not None:
        _, framed = feature_tree._stream_leaf_trees(whole(), PathEncoder.INT_PK_ENCODER)
        assert len(framed) >= max(1, n // 1_000)  # a whole column is cut into batches
    odb = KartRepo.init_repository(str(tmp_path / "merged")).odb
    want = feature_tree.build_int_feature_tree(
        KartRepo.init_repository(str(tmp_path / "plan")).odb, pks, oids
    )
    assert feature_tree.write_int_feature_tree(odb, whole) == want
    assert sum(1 for _ in odb.tree(want).walk_blobs()) == n
    packs = sorted(os.listdir(os.path.join(odb.objects_dir, "pack")))
    shuffled = rng.permutation(n)
    assert feature_tree.write_int_feature_tree(
        odb, lambda: iter([(pks[shuffled], oids[shuffled])])
    ) == want
    assert feature_tree.write_int_feature_tree(odb, in_parts) == want
    assert sorted(os.listdir(os.path.join(odb.objects_dir, "pack"))) == packs


def _merge_inputs(ours, theirs):
    """{key: first oid word} of ours and of theirs (a clean change wherever
    they differ) -> ``_merged_batches``' arguments less the leaf width."""
    o_block, t_block = _revision(ours), _revision(theirs)
    changed = np.array(
        sorted(k for k in set(ours) | set(theirs) if ours.get(k) != theirs.get(k)),
        dtype=np.int64,
    )

    def rows(block, wanted):
        if not block.count:
            return np.full(len(wanted), -1)
        at = np.minimum(np.searchsorted(block.keys, wanted), block.count - 1)
        return np.where(block.keys[at] == wanted, at, -1)

    o_rows, t_rows = rows(o_block, changed), rows(t_block, changed)
    present = t_rows >= 0
    rewrites = o_rows[present] >= 0
    return (
        o_block, t_block, o_rows[present][rewrites], t_rows[present][rewrites],
        o_rows[~present], t_rows[present][~rewrites],
    )


@pytest.mark.parametrize("n_ours", [0, 1, 999, 1000, 1001, 4321], ids=lambda n: f"ours_{n}")
def test_the_merged_columns_come_in_key_order_batch_by_batch(n_ours, monkeypatch):
    """``_merged_batches`` against a dict per key: rewrites, deletes and
    theirs' new keys — below, between and above ours' — land in the batch
    their key belongs to, whatever the batch boundaries cut, and no leaf of
    the feature tree lies in two batches."""
    from kart_tpu.core import feature_tree
    from kart_tpu.merge import _merged_batches

    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1000)
    rng = np.random.default_rng(n_ours)
    ours = {int(k): 1 for k in rng.choice(20_000, n_ours, replace=False) + 100}
    theirs = dict(ours)
    keys = np.array(sorted(ours), dtype=np.int64)
    for k in rng.choice(keys, n_ours // 5, replace=False).tolist():
        theirs[k] = 2
    for k in rng.choice(keys, n_ours // 7, replace=False).tolist():
        theirs.pop(k, None)
    for k in rng.choice(25_000, 300, replace=False).tolist():
        theirs.setdefault(int(k), 3)
    batches = list(_merged_batches(*_merge_inputs(ours, theirs), 64))
    pks = np.concatenate([b[0] for b in batches])
    oids = np.concatenate([b[1] for b in batches])
    assert pks.tolist() == sorted(theirs) and oids.shape == (len(theirs), 20)
    assert oids[:, 0].tolist() == [theirs[k] for k in sorted(theirs)]
    assert all(len(b[0]) for b in batches) and len(batches) >= n_ours // 1000
    assert all(a[0][-1] // 64 < b[0][0] // 64 for a, b in zip(batches, batches[1:]))


# -- the leaf stream on a pool of threads (ISSUE 41) ---------------------------

def _merge_shape(shape):
    """-> (ours, theirs) as {key: first oid word}: 5,000 of ours' rows and
    theirs' clean changes, cut by ``LEAF_STREAM_ROWS`` = 1,000."""
    if shape == "sparse":  # a leaf a row
        ours = {100 + 64 * i: 1 for i in range(5_000)}
    else:  # dense from 7 on: ours' row 1,000 is the 48th of its leaf
        ours = {7 + i: 1 for i in range(5_000)}
    keys = sorted(ours)
    theirs = dict(ours)
    for k in keys[::9]:
        theirs[k] = 2
    if shape == "removed_at_a_batchs_end":  # ours' rows 930..1,069 and the last 80
        for k in keys[930:1_070] + keys[-80:]:
            del theirs[k]
    if shape == "added_past_the_last_key":
        for k in range(keys[-1] + 1, keys[-1] + 1_500):
            theirs[k] = 3
        theirs[keys[-1] + 10_000] = 3
    return ours, theirs


@pytest.fixture
def quick_switches():
    """Threads give way every microsecond: what holds only by luck of the
    scheduler fails here."""
    import sys

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    yield
    sys.setswitchinterval(interval)


def _pack_files(odb):
    pack_dir = os.path.join(odb.objects_dir, "pack")
    out = {}
    for name in sorted(os.listdir(pack_dir)):
        with open(os.path.join(pack_dir, name), "rb") as f:
            out[name] = f.read()
    return out


@pytest.mark.parametrize("workers", [1, 2, 4])
@pytest.mark.parametrize(
    "shape", ["dense", "sparse", "removed_at_a_batchs_end", "added_past_the_last_key"]
)
def test_the_leaf_stream_on_a_pool_names_and_writes_the_plans_tree(
    shape, workers, tmp_path, monkeypatch, quick_switches
):
    """``write_int_feature_tree`` over ``_merged_batches`` with the host's
    cores given as 1, 2 and 4: the root is ``build_int_feature_tree``'s, the
    pack written on a miss is byte for byte the one a single worker writes
    (the same objects in the same order), a second call writes nothing."""
    from kart_tpu import native
    from kart_tpu.core import feature_tree
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.merge import _merged_batches

    if native.load_io() is None:
        pytest.skip("no native IO core: the plan answers, which other tests hold")
    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1_000)
    ours, theirs = _merge_shape(shape)
    inputs = _merge_inputs(ours, theirs)
    assert inputs[0].keys[1_000] % 64 != 0 or shape == "sparse"  # a cut inside a leaf

    def batches():
        return _merged_batches(*inputs, 64)

    pks = np.array(sorted(theirs), dtype=np.int64)
    oids = np.concatenate([b[1] for b in batches()])
    want = feature_tree.build_int_feature_tree(
        KartRepo.init_repository(str(tmp_path / "plan")).odb, pks, oids
    )

    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        monkeypatch.setattr(os, "cpu_count", lambda: workers)
        odb = KartRepo.init_repository(str(tmp_path / "pool")).odb
        with tm.span("merge.apply"):
            assert feature_tree.write_int_feature_tree(odb, batches) == want
        events = tm.drain_events()
        written = _pack_files(odb)
        assert feature_tree.write_int_feature_tree(odb, batches) == want
        assert _pack_files(odb) == written and len(written) == 2  # a pack and its index

        monkeypatch.setattr(os, "cpu_count", lambda: 1)
        alone = KartRepo.init_repository(str(tmp_path / "alone")).odb
        assert feature_tree.write_int_feature_tree(alone, batches) == want
        assert _pack_files(alone) == written
    finally:
        tm.reset()
    assert sum(1 for _ in odb.tree(want).walk_blobs()) == len(theirs)

    leaf_spans = [e for e in events if e["name"] == "merge.leaf_batch"]
    (applied,) = [e for e in events if e["name"] == "merge.apply"]
    assert applied["args"]["workers"] == workers
    assert applied["args"]["leaf_batches"] == len(leaf_spans) >= 5
    assert sum(e["args"]["rows"] for e in leaf_spans) == len(theirs)
    assert sum(e["args"]["leaves"] for e in leaf_spans) == len(np.unique(pks // 64))
    assert all(e["args"]["parent"] == "merge.apply" for e in leaf_spans)
    names = {e["tname"] for e in leaf_spans}
    if workers == 1:
        assert names == {applied["tname"]}
    else:
        assert all(name.startswith("kart-leaf") for name in names)


def test_a_stream_of_one_batch_stays_on_the_calling_thread(tmp_path, monkeypatch):
    from kart_tpu import native
    from kart_tpu.core import feature_tree
    from kart_tpu.core.repo import KartRepo

    if native.load_io() is None:
        pytest.skip("no native IO core")
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    pks = np.arange(900, dtype=np.int64)
    oids = np.full((900, 20), 9, dtype=np.uint8)
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        with tm.span("merge.apply"):
            feature_tree.write_int_feature_tree(
                KartRepo.init_repository(str(tmp_path / "one")).odb,
                lambda: iter([(pks, oids)]),
            )
        events = tm.drain_events()
        counters = tm.counters_snapshot()
    finally:
        tm.reset()
    (leaf,) = [e for e in events if e["name"] == "merge.leaf_batch"]
    (applied,) = [e for e in events if e["name"] == "merge.apply"]
    assert leaf["tname"] == applied["tname"] and leaf["args"]["parent"] == "merge.apply"
    assert (applied["args"]["leaf_batches"], applied["args"]["workers"]) == (1, 1)
    assert (leaf["args"]["rows"], leaf["args"]["leaves"]) == (900, 15)
    assert counters[("merge.leaf_batches", ())] == 1


@pytest.mark.parametrize("why", ["no_native_library", "a_later_batch_out_of_order",
                                 "a_later_batch_out_of_range"])
def test_a_stream_that_does_not_qualify_is_answered_by_the_plan(
    why, tmp_path, monkeypatch, quick_switches
):
    """The two declines: without the native core no batch is made; a batch
    whose pks are out of order (or past the encoder's range) is met by a
    later worker, after earlier batches' results exist. Either way the plan
    names the same tree, whole, and no pool thread is left behind."""
    import threading

    from kart_tpu import native
    from kart_tpu.core import feature_tree
    from kart_tpu.core.repo import KartRepo

    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1_000)
    monkeypatch.setattr(os, "cpu_count", lambda: 2)
    rng = np.random.default_rng(41)
    pks = np.arange(3, 8_003, dtype=np.int64)
    oids = rng.integers(0, 256, (len(pks), 20), dtype=np.uint8)
    if why == "no_native_library":
        monkeypatch.setattr(native, "load_io", lambda: None)
    elif native.load_io() is None:
        pytest.skip("no native IO core: nothing to decline")
    elif why == "a_later_batch_out_of_order":
        pks[[6_500, 6_501]] = pks[[6_501, 6_500]]
    else:
        pks[-1] = 64**6
    want = feature_tree.build_int_feature_tree(
        KartRepo.init_repository(str(tmp_path / "plan")).odb, pks, oids
    )

    def batches():
        for lo in range(0, len(pks), 1_024):  # 16 leaves a batch
            yield pks[lo : lo + 1_024], oids[lo : lo + 1_024]

    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        odb = KartRepo.init_repository(str(tmp_path / "merged")).odb
        assert feature_tree._stream_leaf_trees(
            batches(), feature_tree.PathEncoder.INT_PK_ENCODER
        ) is None
        made = [e["args"] for e in tm.drain_events() if e["name"] == "merge.leaf_batch"]
        assert feature_tree.write_int_feature_tree(odb, batches) == want
    finally:
        tm.reset()
    if why == "no_native_library":
        assert made == []
    else:  # the batches before the bad one were made, whole, before it was met
        assert sum("leaves" in args for args in made) >= 6
        assert sum("leaves" not in args for args in made) == 1
    assert sum(1 for _ in odb.tree(want).walk_blobs()) == len(pks)
    assert not [t for t in threading.enumerate() if t.name.startswith("kart-leaf")]


def test_batches_that_end_inside_a_leaf_are_cut_again_and_lose_no_row(monkeypatch):
    """``_cut_on_leaves``: batches as a caller may hand them over — a whole
    column, pieces that end inside a leaf, a piece of one row, an empty one
    — come out in key order, at most ``LEAF_STREAM_ROWS`` rows each, no leaf
    in two of them; batches that end where a leaf ends are passed on as
    views of what came in."""
    from kart_tpu.core import feature_tree

    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1_000)
    rng = np.random.default_rng(7)
    pks = np.sort(rng.choice(30_000, 9_000, replace=False)).astype(np.int64)
    oids = rng.integers(0, 256, (len(pks), 20), dtype=np.uint8)
    for edges in ([0, 9_000], [0, 1, 1, 700, 701, 5_000, 9_000], list(range(0, 9_001, 450))):
        got = list(feature_tree._cut_on_leaves(
            ((pks[a:b], oids[a:b]) for a, b in zip(edges, edges[1:])), 64
        ))
        np.testing.assert_array_equal(np.concatenate([g[0] for g in got]), pks)
        np.testing.assert_array_equal(np.concatenate([g[1] for g in got]), oids)
        assert all(0 < len(g[0]) <= 1_000 for g in got)
        assert all(a[0][-1] // 64 < b[0][0] // 64 for a, b in zip(got, got[1:]))
    aligned = [int(np.searchsorted(pks, leaf * 64)) for leaf in (0, 5, 11, 17, 40, 469)]
    got = list(feature_tree._cut_on_leaves(
        ((pks[a:b], oids[a:b]) for a, b in zip(aligned, aligned[1:])), 64
    ))
    assert all(np.shares_memory(g[0], pks) and np.shares_memory(g[1], oids) for g in got)


# -- the CLI merge on every route ---------------------------------------------

def _load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"merge_route_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


PARAMS = {
    "geometry": "POINT", "rows": 3000, "envelopes": True,
    "edit_edit_frac": 0.225, "edit_delete_frac": 0.0125, "add_add_frac": 0.0125,
    "same_frac": 0.03125, "theirs_edit_frac": 0.0625, "theirs_delete_frac": 0.03125,
    "theirs_insert_frac": 0.03125, "ours_edit_frac": 0.0625,
}


@pytest.fixture(scope="module")
def merge_layer(tmp_path_factory):
    """The benchmark's merge deployment at 3,000 rows: -> (builder, base dir)."""
    builder = _load("layers", "int_pk_merge_layer")
    base = tmp_path_factory.mktemp("merge-base")
    builder.build_base(str(base), PARAMS)
    return builder, str(base)


def _merge(repo_path, *, env=None, dry_run=False):
    """`kart merge theirs` -> (exit code, stdout, MERGE_INDEX bytes | None,
    span events, counters), the merge aborted again."""
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    import json

    trace_path = os.path.join(repo_path, "spans.json")
    tm.reset()
    tm.enable(metrics=True, trace=True, trace_path=trace_path)
    args = ["-C", repo_path, "merge", "theirs"] + (
        ["--dry-run", "-o", "json"] if dry_run else []
    )
    result = CliRunner().invoke(cli, args, env=env, catch_exceptions=False)
    with open(trace_path) as f:  # the CLI writes its spans as the command closes
        events = json.load(f)["traceEvents"]
    counters = tm.counters_snapshot()
    tm.enable(trace=False)
    index_path = os.path.join(repo_path, ".kart", "MERGE_INDEX")
    raw = None
    if os.path.exists(index_path):
        with open(index_path, "rb") as f:
            raw = f.read()
        CliRunner().invoke(cli, ["-C", repo_path, "merge", "--abort"],
                           catch_exceptions=False)
    tm.reset()
    return result.exit_code, result.stdout_bytes, raw, events, counters


HOST = {"KART_DIFF_BACKEND": "host_native", "KART_DIFF_DEVICE": "0",
        "KART_DIFF_SHARDED": "0"}
DEVICE = {"KART_DIFF_DEVICE": "1", "KART_DIFF_SHARDED": "0"}


@pytest.mark.parametrize("apply_route", ["whole_tree", "by_path"])
def test_a_cli_merge_is_the_same_on_every_route(apply_route, merge_layer, tmp_path, monkeypatch):
    """MERGE_INDEX bytes and the merged tree are the same on the device
    route, on the host engine and with blocks made by walking the feature
    trees (the parent's way); the answer is the builder's; with sidecars
    there no tree is walked. Both forms of the apply — the merged feature
    tree made whole from the merged columns, the changed paths handed to the
    tree builder — write the same tree."""
    import kart_tpu.merge as merge_module
    from kart_tpu.diff import sidecar

    builder, base = merge_layer
    reference = _load("references", "merge_conflicts")
    repo_path, info = builder.add_edit_commit(base, str(tmp_path), PARAMS, 2147483659)
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 30))
    if apply_route == "by_path":
        monkeypatch.setattr(merge_module, "REBUILD_MIN_SHARE", 0)

    code, _, device_raw, events, counters = _merge(repo_path, env=DEVICE)
    assert code == 0 and device_raw is not None
    (merge,) = [e["args"] for e in events if e["name"] == "diff.merge_classify"]
    assert merge["backend"] == "device_jax"
    (load,) = [e["args"] for e in events if e["name"] == "merge.load_blocks"]
    assert load["source"] == "sidecar"
    assert (load["rows_ancestor"], load["rows_ours"]) == (3000, 3037)
    assert counters[("merge.tree_walk_rows", ())] == 0
    assert counters[("merge.conflicts", ())] == info["conflicts"] == 749
    assert counters[("merge.take_theirs", ())] == info["take_theirs"] == 373
    (applied,) = [e["args"] for e in events if e["name"] == "merge.apply"]
    assert applied["take_theirs"] == 373
    assert (applied["inserted"], applied["removed"]) == (187 + 93, 93)
    assert applied["trees_written"] == (1 if apply_route == "whole_tree" else 0)
    (conflicts,) = [e["args"] for e in events if e["name"] == "merge.conflicts"]
    assert conflicts["conflicts"] == 749
    assert [e for e in events if e["name"] == "merge.non_features"]
    (written,) = [e["args"] for e in events if e["name"] == "merge.write_tree"]
    assert written["changes"] == (1 if apply_route == "whole_tree" else 373)

    from kart_tpu.core.repo import KartRepo

    checks = reference.check_index(device_raw, info, KartRepo(repo_path).odb)
    assert checks and all(checks.values()), checks

    code, _, host_raw, events, _ = _merge(repo_path, env=HOST)
    (merge,) = [e["args"] for e in events if e["name"] == "diff.merge_classify"]
    assert code == 0 and merge["backend"] == "host_native"
    assert host_raw == device_raw

    # the parent's blocks: no sidecar is read, every feature tree is walked
    monkeypatch.setattr(sidecar, "has_sidecar", lambda repo, ds: False)
    monkeypatch.setattr(sidecar, "ensure_block", lambda repo, ds, pad=True: None)
    code, _, walked_raw, events, counters = _merge(repo_path, env=HOST)
    (load,) = [e["args"] for e in events if e["name"] == "merge.load_blocks"]
    assert code == 0 and load["source"] == "tree_walk"
    assert counters[("merge.tree_walk_rows", ())] == 3000 + 3037 + 3000
    assert walked_raw == device_raw


def test_a_dry_run_names_the_conflicts_and_changes_nothing(merge_layer, tmp_path):
    builder, base = merge_layer
    reference = _load("references", "merge_conflicts")
    repo_path, info = builder.add_edit_commit(base, str(tmp_path), PARAMS, 2147483777)
    code, stdout, raw, events, _ = _merge(repo_path, env=DEVICE, dry_run=True)
    assert code == 0 and raw is None
    import json

    assert json.loads(stdout) == reference.expected_document(info)
    assert reference._is_normal(repo_path, info)
    code, twin_stdout, _, _, _ = _merge(repo_path, env=HOST, dry_run=True)
    assert code == 0 and twin_stdout == stdout


@pytest.mark.parametrize("workers", [1, 3])
def test_a_commands_leaf_batches_are_its_own_spans(workers, merge_layer, tmp_path, monkeypatch):
    """``kart merge --dry-run`` with spans recorded: every ``merge.leaf_batch``
    names ``merge.apply`` as its parent and carries the command's trace id,
    on whichever thread it ran; their rows and leaves add up to the merged
    layer's; ``merge.apply`` says how many batches and workers there were.
    With one worker the spans are emitted all the same."""
    from kart_tpu import native
    from kart_tpu.core import feature_tree

    if native.load_io() is None:
        pytest.skip("no native IO core: the plan makes the tree, no batch is made")
    builder, base = merge_layer
    repo_path, info = builder.add_edit_commit(base, str(tmp_path), PARAMS, 2147484041)
    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 500)
    monkeypatch.setattr(os, "cpu_count", lambda: workers)
    code, _, _, events, counters = _merge(repo_path, env=HOST, dry_run=True)
    assert code == 0
    (command,) = [e for e in events if e["name"] == "cli.command"]
    (applied,) = [e for e in events if e["name"] == "merge.apply"]
    batches = [e for e in events if e["name"] == "merge.leaf_batch"]
    assert len(batches) >= 6
    assert (applied["args"]["leaf_batches"], applied["args"]["workers"]) == (len(batches), workers)
    assert counters[("merge.leaf_batches", ())] == len(batches)
    for e in batches:
        assert e["args"]["parent"] == "merge.apply"
        assert e["args"]["trace_id"] == command["args"]["trace_id"]
        assert applied["ts"] <= e["ts"] and e["ts"] + e["dur"] <= applied["ts"] + applied["dur"]
        assert (e["tid"] == applied["tid"]) is (workers == 1)
    merged_pks = np.asarray(info["merged_pks"])
    assert sum(e["args"]["rows"] for e in batches) == len(merged_pks)
    assert sum(e["args"]["leaves"] for e in batches) == len(np.unique(merged_pks // 64))
    assert sum(e["args"]["bytes"] for e in batches) >= 30 * len(merged_pks)  # an entry a row


def test_a_tree_without_a_sidecar_is_walked_once_and_its_sidecar_kept(merge_layer, tmp_path):
    """First use: theirs' sidecar is gone, the merge walks that one tree,
    saves its sidecar, and the next merge walks nothing."""
    builder, base = merge_layer
    repo_path, info = builder.add_edit_commit(base, str(tmp_path), PARAMS, 2147483888)
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.structure import RepoStructure
    from kart_tpu.diff import sidecar

    repo = KartRepo(repo_path)
    theirs = RepoStructure(repo, "theirs").datasets["layer"]
    os.remove(sidecar.sidecar_file(repo, theirs.feature_tree.oid))
    code, first, _, events, counters = _merge(repo_path, env=HOST, dry_run=True)
    (load,) = [e["args"] for e in events if e["name"] == "merge.load_blocks"]
    assert code == 0 and load["source"] == "tree_walk"
    assert counters[("merge.tree_walk_rows", ())] == load["rows_theirs"] == 3000
    code, second, _, events, counters = _merge(repo_path, env=HOST, dry_run=True)
    (load,) = [e["args"] for e in events if e["name"] == "merge.load_blocks"]
    assert load["source"] == "sidecar" and counters[("merge.tree_walk_rows", ())] == 0
    assert second == first
