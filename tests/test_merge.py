"""3-way merge: kernel bit-compat, fast-forward, clean merge, conflicts,
resolve, --continue/--abort, state machine (reference: tests/test_merge.py,
tests/test_conflicts.py, tests/test_resolve.py)."""

import json

import numpy as np
import pytest

from helpers import edit_commit, make_imported_repo
from kart_tpu.core.repo import InvalidOperation, KartRepoState
from kart_tpu.geometry import Geometry
from kart_tpu.merge import (
    abort_merging_state,
    complete_merging_state,
    do_merge,
)
from kart_tpu.merge.index import ConflictEntry, MergeIndex
from kart_tpu.ops.blocks import FeatureBlock
from kart_tpu.diff.backend import merge_classify
from kart_tpu.ops.merge_kernel import (
    CONFLICT,
    KEEP_OURS,
    TAKE_THEIRS,
    merge_classify_reference,
)


def _block(items):
    """{key: oid_byte} -> FeatureBlock with synthetic 20-byte oids."""
    keys = np.asarray(sorted(items), dtype=np.int64)
    oids = np.zeros((len(keys), 5), dtype=np.uint32)
    for i, k in enumerate(keys):
        oids[i, :] = items[k]
    paths = [f"p{k}" for k in keys]
    return FeatureBlock.from_arrays(keys, oids, paths)


class TestMergeKernel:
    def test_classic_rules(self):
        #       key: 1 unchanged, 2 theirs-edit, 3 ours-edit, 4 both-same-edit,
        #            5 conflict-edit, 6 theirs-delete, 7 ours-insert,
        #            8 theirs-insert, 9 both-insert-same, 10 both-insert-diff
        a = _block({1: 1, 2: 2, 3: 3, 4: 4, 5: 5, 6: 6})
        o = _block({1: 1, 2: 2, 3: 33, 4: 44, 5: 55, 6: 6, 7: 7, 9: 9, 10: 100})
        # key 6 absent from theirs (theirs-delete)
        t = _block({1: 1, 2: 22, 3: 3, 4: 44, 5: 555, 8: 8, 9: 9, 10: 101})

        union, decision, presence, stats = merge_classify(a, o, t)
        by_key = dict(zip(union.tolist(), decision.tolist()))
        assert by_key[1] == KEEP_OURS
        assert by_key[2] == TAKE_THEIRS
        assert by_key[3] == KEEP_OURS
        assert by_key[4] == KEEP_OURS  # same edit both sides
        assert by_key[5] == CONFLICT
        assert by_key[6] == TAKE_THEIRS  # theirs deleted
        assert by_key[7] == KEEP_OURS  # ours insert
        assert by_key[8] == TAKE_THEIRS  # theirs insert
        assert by_key[9] == KEEP_OURS  # same insert
        assert by_key[10] == CONFLICT  # add/add different
        assert stats["conflicts"] == 2

    def test_matches_reference(self):
        rng = np.random.default_rng(3)
        n = 500
        base = {int(k): int(v) for k, v in zip(rng.choice(5000, n, replace=False), rng.integers(1, 2**31, n))}
        ours = dict(base)
        theirs = dict(base)
        for k in list(base)[:50]:
            ours[k] = int(rng.integers(1, 2**31))
        for k in list(base)[30:80]:
            theirs[k] = int(rng.integers(1, 2**31))
        for k in list(base)[100:120]:
            del ours[k]
        for k in list(base)[110:130]:
            del theirs[k]
        a_b, o_b, t_b = _block(base), _block(ours), _block(theirs)
        union, decision, presence, _ = merge_classify(a_b, o_b, t_b)
        ref_union, ref_decision, ref_presence = merge_classify_reference(a_b, o_b, t_b)
        assert np.array_equal(union, ref_union)
        assert np.array_equal(decision, ref_decision)
        assert np.array_equal(presence, ref_presence)


@pytest.fixture
def branched_repo(tmp_path):
    """repo with main (theirs edits) and branch 'ours' checked out."""
    repo, ds_path = make_imported_repo(tmp_path, n=10)
    base_oid = repo.head_commit_oid
    # create branch alt from base
    repo.refs.set("refs/heads/alt", base_oid)
    return repo, ds_path, base_oid


def _feature(fid, name, rating=1.0, x=100.0, y=-40.0):
    return {
        "fid": fid,
        "geom": Geometry.from_wkt(f"POINT ({x} {y})"),
        "name": name,
        "rating": rating,
    }


class TestDoMerge:
    def test_fast_forward(self, branched_repo):
        repo, ds_path, base = branched_repo
        edit_commit(repo, ds_path, inserts=[_feature(50, "new")])
        head = repo.head_commit_oid
        # reset HEAD branch back to base, then merge the edit commit
        branch = repo.head_branch
        repo.refs.set(branch, base)
        result = do_merge(repo, head)
        assert result.fast_forward
        assert repo.head_commit_oid == head

    def test_already_merged(self, branched_repo):
        repo, ds_path, base = branched_repo
        edit_commit(repo, ds_path, inserts=[_feature(50, "new")])
        result = do_merge(repo, base)
        assert result.already_merged

    def test_clean_merge(self, branched_repo):
        repo, ds_path, base = branched_repo
        # ours: edit fid 2 on main
        edit_commit(repo, ds_path, updates=[_feature(2, "ours-2", 2.0)])
        # theirs: edit fid 3 + insert 60 on alt
        edit_commit(
            repo,
            ds_path,
            updates=[_feature(3, "theirs-3", 3.0)],
            inserts=[_feature(60, "theirs-60")],
            ref="refs/heads/alt",
        )
        result = do_merge(repo, "alt")
        assert not result.has_conflicts
        assert result.commit_oid
        commit = repo.odb.read_commit(result.commit_oid)
        assert len(commit.parents) == 2
        merged = repo.datasets(result.commit_oid)[ds_path]
        assert merged.get_feature([2])["name"] == "ours-2"
        assert merged.get_feature([3])["name"] == "theirs-3"
        assert merged.get_feature([60])["name"] == "theirs-60"
        assert repo.state == KartRepoState.NORMAL

    def test_conflicting_merge_and_resolve(self, branched_repo):
        repo, ds_path, base = branched_repo
        edit_commit(repo, ds_path, updates=[_feature(4, "ours-4")])
        edit_commit(
            repo, ds_path, updates=[_feature(4, "theirs-4")], ref="refs/heads/alt"
        )
        result = do_merge(repo, "alt")
        assert result.has_conflicts
        assert repo.state == KartRepoState.MERGING
        label = f"{ds_path}:feature:4"
        assert list(result.merge_index.conflicts) == [label]

        # cannot merge again while merging
        with pytest.raises(InvalidOperation):
            do_merge(repo, "alt")
        # cannot continue while unresolved
        with pytest.raises(InvalidOperation):
            complete_merging_state(repo)

        # resolve with theirs
        merge_index = MergeIndex.read_from_repo(repo)
        aot = merge_index.conflicts[label]
        merge_index.add_resolve(label, [aot.theirs])
        merge_index.write_to_repo(repo)

        commit_oid = complete_merging_state(repo)
        assert repo.state == KartRepoState.NORMAL
        merged = repo.datasets(commit_oid)[ds_path]
        assert merged.get_feature([4])["name"] == "theirs-4"
        commit = repo.odb.read_commit(commit_oid)
        assert len(commit.parents) == 2

    def test_resolve_with_delete(self, branched_repo):
        repo, ds_path, base = branched_repo
        edit_commit(repo, ds_path, updates=[_feature(4, "ours-4")])
        edit_commit(
            repo, ds_path, updates=[_feature(4, "theirs-4")], ref="refs/heads/alt"
        )
        do_merge(repo, "alt")
        label = f"{ds_path}:feature:4"
        merge_index = MergeIndex.read_from_repo(repo)
        merge_index.add_resolve(label, [])
        merge_index.write_to_repo(repo)
        commit_oid = complete_merging_state(repo)
        merged = repo.datasets(commit_oid)[ds_path]
        with pytest.raises(KeyError):
            merged.get_feature([4])
        assert merged.feature_count == 9

    def test_abort(self, branched_repo):
        repo, ds_path, base = branched_repo
        head_before = None
        edit_commit(repo, ds_path, updates=[_feature(4, "ours-4")])
        head_before = repo.head_commit_oid
        edit_commit(
            repo, ds_path, updates=[_feature(4, "theirs-4")], ref="refs/heads/alt"
        )
        do_merge(repo, "alt")
        assert repo.state == KartRepoState.MERGING
        abort_merging_state(repo)
        assert repo.state == KartRepoState.NORMAL
        assert repo.head_commit_oid == head_before

    def test_delete_edit_conflict(self, branched_repo):
        repo, ds_path, base = branched_repo
        edit_commit(repo, ds_path, deletes=[6])
        edit_commit(
            repo, ds_path, updates=[_feature(6, "theirs-6")], ref="refs/heads/alt"
        )
        result = do_merge(repo, "alt")
        assert result.has_conflicts
        label = f"{ds_path}:feature:6"
        aot = result.merge_index.conflicts[label]
        assert aot.ours is None  # deleted in ours
        assert aot.theirs is not None
        assert aot.ancestor is not None

    def test_meta_conflict(self, branched_repo):
        repo, ds_path, base = branched_repo
        from kart_tpu.diff.structs import (
            DatasetDiff,
            Delta,
            DeltaDiff,
            KeyValue,
            RepoDiff,
        )

        def meta_commit(title, ref):
            structure = repo.structure(ref)
            meta_diff = DeltaDiff()
            meta_diff.add_delta(
                Delta.update(
                    KeyValue(("title", "points title")), KeyValue(("title", title))
                )
            )
            ds_diff = DatasetDiff()
            ds_diff["meta"] = meta_diff
            repo_diff = RepoDiff()
            repo_diff[ds_path] = ds_diff
            return structure.commit_diff(repo_diff, f"retitle {title}")

        meta_commit("ours title", "HEAD")
        meta_commit("theirs title", "refs/heads/alt")
        result = do_merge(repo, "alt")
        assert result.has_conflicts
        assert f"{ds_path}:meta:title" in result.merge_index.conflicts

    def test_merge_dry_run(self, branched_repo):
        repo, ds_path, base = branched_repo
        head_before = repo.head_commit_oid
        edit_commit(
            repo, ds_path, updates=[_feature(3, "theirs-3")], ref="refs/heads/alt"
        )
        result = do_merge(repo, "alt", dry_run=True)
        assert result.dry_run
        assert repo.head_commit_oid == head_before
        assert repo.state == KartRepoState.NORMAL


class TestConflictMaterialisation:
    """Batched conflict materialisation (BASELINE config #5 path)."""

    def _block(self, keys, oid_salt, paths):
        from kart_tpu.ops.blocks import FeatureBlock, bucket_size, PAD_KEY

        keys = np.asarray(keys, dtype=np.int64)
        n = len(keys)
        rng = np.random.default_rng(0)
        oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
        oids[:, 0] ^= oid_salt
        block = FeatureBlock.__new__(FeatureBlock)
        size = bucket_size(max(n, 1))
        if size > n:
            keys = np.concatenate([keys, np.full(size - n, PAD_KEY, np.int64)])
            oids = np.concatenate([oids, np.zeros((size - n, 5), np.uint32)])
        block.keys = keys
        block.oids = oids
        block.paths = list(paths)
        block.count = n
        return block

    def test_labels_decode_with_each_versions_encoder(self):
        """Every conflict label must decode the rel path with the encoder of
        the version the path came from — a pk-type change means versions of
        one dataset can carry different path encodings, and decoding hash
        paths with the int encoder would collapse labels (and so conflicts)."""
        from kart_tpu.merge import materialise_conflicts
        from kart_tpu.models.paths import PathEncoder
        from kart_tpu.diff.backend import merge_classify
        from kart_tpu.ops.merge_kernel import CONFLICT

        int_enc = PathEncoder.INT_PK_ENCODER
        keys = np.arange(4, dtype=np.int64)
        int_paths = int_enc.encode_paths_batch(keys)

        class _IntDs:
            path_encoder = int_enc

            @staticmethod
            def decode_path_to_pks(rel):
                return int_enc.decode_path_to_pks(rel)

        a = self._block(keys, 0, int_paths)
        o = self._block(keys, 1, int_paths)  # every row changed in ours
        t = self._block(keys, 2, int_paths)  # ... and differently in theirs
        union, decision, _, stats = merge_classify(a, o, t)
        conflict_idx = np.nonzero(decision == CONFLICT)[0]
        assert len(conflict_idx) == 4

        conflicts = materialise_conflicts(
            "ds", [a, o, t], [_IntDs(), _IntDs(), _IntDs()], "inner",
            union, conflict_idx,
        )
        # distinct, correctly-decoded labels — one per conflicting pk
        assert sorted(conflicts) == [f"ds:feature:{k}" for k in range(4)]
        for label, aot in conflicts.items():
            assert aot.ancestor is not None
            assert aot.ours is not None and aot.theirs is not None
            assert aot.ours.path.startswith("inner/feature/")

    def test_labels_mixed_encoders_decode_real_pks(self):
        """A pk-type change leaves versions with different encoders; a
        conflict present only in the hash-keyed versions must still be
        labelled with its decoded pk, not the internal 63-bit hash key."""
        from kart_tpu.merge import materialise_conflicts
        from kart_tpu.models.paths import PathEncoder
        from kart_tpu.ops.blocks import hash_keys_for_paths
        from kart_tpu.diff.backend import merge_classify
        from kart_tpu.ops.merge_kernel import CONFLICT

        int_enc = PathEncoder.INT_PK_ENCODER
        hash_enc = PathEncoder.GENERAL_ENCODER

        pks = [101, 202, 303]
        hash_paths = [hash_enc.encode_pks_to_path((pk,)) for pk in pks]
        order = np.argsort(hash_keys_for_paths(hash_paths))
        hash_paths = [hash_paths[i] for i in order]
        keys = np.sort(hash_keys_for_paths(hash_paths))

        class _IntDs:
            path_encoder = int_enc

        class _HashDs:
            path_encoder = hash_enc

            @staticmethod
            def decode_path_to_pks(rel):
                return hash_enc.decode_path_to_pks(rel)

        a = self._block(np.zeros(0, dtype=np.int64), 0, [])
        o = self._block(keys, 1, hash_paths)
        t = self._block(keys, 2, hash_paths)
        union, decision, _, _ = merge_classify(a, o, t)
        conflict_idx = np.nonzero(decision == CONFLICT)[0]
        assert len(conflict_idx) == 3

        conflicts = materialise_conflicts(
            "ds", [a, o, t], [_IntDs(), _HashDs(), _HashDs()], "inner",
            union, conflict_idx,
        )
        assert sorted(conflicts) == sorted(f"ds:feature:{pk}" for pk in pks)

    def test_labels_fall_back_per_version_without_encoder(self):
        """datasets=None versions still label every conflict distinctly."""
        from kart_tpu.merge import materialise_conflicts
        from kart_tpu.diff.backend import merge_classify
        from kart_tpu.ops.merge_kernel import CONFLICT

        keys = np.arange(3, dtype=np.int64)
        paths = [f"aa/k{k}" for k in keys]
        a = self._block(keys, 0, paths)
        o = self._block(keys, 1, paths)
        t = self._block(keys, 2, paths)
        union, decision, _, _ = merge_classify(a, o, t)
        conflict_idx = np.nonzero(decision == CONFLICT)[0]
        conflicts = materialise_conflicts(
            "ds", [a, o, t], [None, None, None], "inner", union, conflict_idx
        )
        assert len(conflicts) == 3
        assert all(label.startswith("ds:feature:") for label in conflicts)


def test_merge_index_binary_roundtrip(tmp_path, monkeypatch):
    """Above the threshold MERGE_INDEX is written as the columnar binary
    format; reading detects the encoding and rebuilds identically."""
    import kart_tpu.merge.index as index_mod
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.merge.index import AncestorOursTheirs, ConflictEntry

    monkeypatch.setattr(index_mod, "_BINARY_THRESHOLD", 3)
    repo = KartRepo.init_repository(tmp_path / "r")
    conflicts = {}
    for i in range(5):
        entry = lambda v: ConflictEntry(f"ds/.table-dataset/feature/aa/k{i}", f"{v:040x}")
        conflicts[f"ds:feature:{i}"] = AncestorOursTheirs(
            entry(i), entry(i + 1), None if i == 2 else entry(i + 2)
        )
    mi = MergeIndex("c" * 40, conflicts)
    mi.add_resolve("ds:feature:1", [ConflictEntry("p", "d" * 40)])
    mi.write_to_repo(repo)

    raw = open(repo.gitdir_file("MERGE_INDEX"), "rb").read()
    assert raw.startswith(b"KMIX2\n")

    mi2 = MergeIndex.read_from_repo(repo)
    assert mi2.merged_tree == mi.merged_tree
    assert sorted(mi2.conflicts) == sorted(mi.conflicts)
    assert mi2.conflicts["ds:feature:2"].theirs is None
    got = mi2.conflicts["ds:feature:4"]
    assert got.ours.path == conflicts["ds:feature:4"].ours.path
    assert got.ours.oid == conflicts["ds:feature:4"].ours.oid
    assert mi2.resolves["ds:feature:1"][0].oid == "d" * 40

    # below the threshold stays JSON
    monkeypatch.setattr(index_mod, "_BINARY_THRESHOLD", 1000)
    mi.write_to_repo(repo)
    raw = open(repo.gitdir_file("MERGE_INDEX"), "rb").read()
    assert raw.lstrip().startswith(b"{")
    mi3 = MergeIndex.read_from_repo(repo)
    assert sorted(mi3.conflicts) == sorted(mi.conflicts)


def test_merge_index_kmix1_backcompat():
    """A KMIX1 file (pre-dedup format: every version carries its own full
    path block) still reads — merges left in progress across an upgrade
    must survive."""
    import json as _json
    import struct as _struct

    import numpy as np

    from kart_tpu.merge.index import MergeIndex

    header = _json.dumps(
        {"mergedTree": "b" * 40, "n": 2, "resolves": {}}
    ).encode()
    labels = b"ds:feature:0\x00ds:feature:1"
    paths = b"ds/.table-dataset/feature/aa/k0\x00ds/.table-dataset/feature/aa/k1"
    blocks = [labels]
    for v in range(3):
        present = bytes([1, 1])
        oids = np.full((2, 20), v + 1, dtype=np.uint8).tobytes()
        blocks += [present, oids, paths]
    raw = b"KMIX1\n" + _struct.pack("<I", len(header)) + header
    for b in blocks:
        raw += _struct.pack("<Q", len(b)) + b
    mi = MergeIndex._from_binary(raw)
    assert sorted(mi.conflicts) == ["ds:feature:0", "ds:feature:1"]
    aot = mi.conflicts["ds:feature:1"]
    assert aot.ancestor.oid == "01" * 20
    assert aot.theirs.oid == "03" * 20
    assert aot.ours.path == "ds/.table-dataset/feature/aa/k1"


def test_columnar_conflicts_mapping_and_binary():
    """materialise_conflicts returns a columnar mapping whose entries,
    iteration order and parsed KMIX2 form match the equivalent plain-dict index —
    including rows absent from some versions (delete/edit conflicts)."""
    import numpy as np

    from kart_tpu.merge import materialise_conflicts
    from kart_tpu.merge.index import (
        AncestorOursTheirs,
        ColumnarConflicts,
        ConflictEntry,
    )
    from kart_tpu.models.paths import PathEncoder
    from kart_tpu.ops.blocks import FeatureBlock

    encoder = PathEncoder.INT_PK_ENCODER

    def block(keys_oids):
        keys = np.array(sorted(keys_oids), dtype=np.int64)
        oids = np.zeros((len(keys), 5), dtype=np.uint32)
        for i, k in enumerate(keys):
            oids[i, 0] = keys_oids[k]
        paths = [encoder.encode_pks_to_path((int(k),)) for k in keys]
        return FeatureBlock.from_arrays(keys, oids, paths)

    # pk 1: edit/edit conflict; pk 2: delete(ours)/edit(theirs);
    # pk 3: edit(ours)/delete(theirs)
    a = block({1: 10, 2: 20, 3: 30})
    o = block({1: 11, 3: 31})
    t = block({1: 12, 2: 22})

    class _Ds:
        path_encoder = encoder

    union = np.array([1, 2, 3], dtype=np.int64)
    conflict_idx = np.arange(3)
    cc = materialise_conflicts(
        "ds", [a, o, t], [_Ds(), _Ds(), _Ds()], "inner", union, conflict_idx
    )
    assert isinstance(cc, ColumnarConflicts)
    assert len(cc) == 3
    assert list(cc) == ["ds:feature:1", "ds:feature:2", "ds:feature:3"]
    assert "ds:feature:2" in cc and "ds:feature:99" not in cc

    aot = cc["ds:feature:2"]
    assert aot.ours is None  # deleted in ours
    assert aot.ancestor.oid.startswith("14")  # 20 -> 0x14 first byte LE word
    assert aot.theirs.path == "inner/feature/" + encoder.encode_pks_to_path((2,))

    # a plain-dict build of the same conflicts parses back identically
    # (byte streams may differ: columnar int-pk columns serialise as KMIX2
    # derived blocks, dict columns as joined path strings)
    dict_conflicts = {label: aot for label, aot in cc.items()}
    raw_columnar = MergeIndex("a" * 40, cc)._to_binary()
    raw_dict = MergeIndex("a" * 40, dict_conflicts)._to_binary()
    parsed_c = MergeIndex._from_binary(raw_columnar)
    parsed_d = MergeIndex._from_binary(raw_dict)
    assert list(parsed_c.conflicts) == list(parsed_d.conflicts)
    for label in parsed_c.conflicts:
        c_aot, d_aot = parsed_c.conflicts[label], parsed_d.conflicts[label]
        for name in ("ancestor", "ours", "theirs"):
            ce, de = c_aot.get(name), d_aot.get(name)
            assert (ce is None) == (de is None), (label, name)
            if ce is not None:
                assert ce.path == de.path and ce.oid == de.oid, (label, name)

    mi2 = MergeIndex._from_binary(raw_columnar)
    assert isinstance(mi2.conflicts, ColumnarConflicts)
    assert list(mi2.conflicts) == list(cc)
    got = mi2.conflicts["ds:feature:3"]
    assert got.theirs is None and got.ours.oid == cc["ds:feature:3"].ours.oid
    # rewrite of a read-back index is byte-identical (resolve flow)
    assert MergeIndex("a" * 40, mi2.conflicts)._to_binary() == raw_columnar


def test_encode_paths_joined_bytes_matches_batch():
    import numpy as np

    from kart_tpu.models.paths import PathEncoder

    enc = PathEncoder.INT_PK_ENCODER
    pks = np.array([0, 1, 127, 128, 255, 65535, 2**31, 2**63 - 1, -1, -129], dtype=np.int64)
    joined = enc.encode_paths_joined_bytes(pks, prefix=b"pre/", sep=b"\x00")
    expected = "\x00".join("pre/" + p for p in enc.encode_paths_batch(pks)).encode()
    assert joined == expected
    assert enc.encode_paths_joined_bytes(np.zeros(0, dtype=np.int64)) == b""
