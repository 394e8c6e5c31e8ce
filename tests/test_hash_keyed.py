"""Hash-keyed (msgpack/hash) datasets on the normal path: the column-wise tree
writer against the per-path TreeBuilder, the sidecar a commit derives against
one rebuilt from the tree, the fast ``-o feature-count`` route against a plain
reference on the host engine and on the device route, and the collision
guards — forced by taking the key width down — each falling back to the
exact path and still answering exactly.

The reference reads a revision as ``{pk: blob oid}`` by walking its feature
tree with the object store's raw reads and decoding every filename itself
(base64, msgpack), then compares two revisions by pk: no blocks, sidecars,
hash keys or kernels."""

import base64
import os
import sqlite3
import uuid

import msgpack
import numpy as np
import pytest
from click.testing import CliRunner

import kart_tpu.importer.importer as importer_mod
from kart_tpu import telemetry as tm
from kart_tpu.core.feature_tree import write_hash_feature_tree
from kart_tpu.core.tree_builder import TreeBuilder
from kart_tpu.diff import engine, sidecar
from kart_tpu.geometry import Geometry
from kart_tpu.models.paths import (
    ByteRows,
    PathEncoder,
    hash_feature_rows,
    msgpack_pk_rows,
)
from kart_tpu.ops import blocks, resident

from helpers import edit_commit, gpkg_point

ENC = PathEncoder.GENERAL_ENCODER
DS = "things"


# -- the plain reference --------------------------------------------------------

def reference_index(repo, rev):
    """{pk tuple: blob oid hex} of the dataset at ``rev``, from raw tree
    objects: mode, name, oid per entry; a blob's name decoded as
    msgpack(b64)."""
    out = {}

    def walk(oid):
        kind, content = repo.odb.read_raw(oid)
        assert kind == "tree"
        i = 0
        while i < len(content):
            sp = content.index(b" ", i)
            nul = content.index(b"\x00", sp)
            mode, name = content[i:sp], content[sp + 1 : nul]
            child = content[nul + 1 : nul + 21].hex()
            if mode == b"40000":
                walk(child)
            else:
                pk = msgpack.unpackb(base64.urlsafe_b64decode(name), raw=False)
                out[tuple(pk)] = child
            i = nul + 21

    walk(repo.structure(rev).datasets[DS].feature_tree.oid)
    return out


def reference_count(repo, a, b):
    old, new = reference_index(repo, a), reference_index(repo, b)
    return sum(
        1 for pk in old.keys() | new.keys() if old.get(pk) != new.get(pk)
    )


# -- repositories ------------------------------------------------------------------

def text_pk_gpkg(path, ids):
    """A GPKG point table keyed by a text pk."""
    con = sqlite3.connect(path)
    con.executescript(
        """
        CREATE TABLE gpkg_contents (
            table_name TEXT NOT NULL PRIMARY KEY, data_type TEXT NOT NULL,
            identifier TEXT UNIQUE, description TEXT DEFAULT '',
            last_change DATETIME, min_x DOUBLE, min_y DOUBLE,
            max_x DOUBLE, max_y DOUBLE, srs_id INTEGER);
        CREATE TABLE gpkg_geometry_columns (
            table_name TEXT NOT NULL, column_name TEXT NOT NULL,
            geometry_type_name TEXT NOT NULL, srs_id INTEGER NOT NULL,
            z TINYINT NOT NULL, m TINYINT NOT NULL,
            CONSTRAINT pk_geom_cols PRIMARY KEY (table_name, column_name));
        CREATE TABLE gpkg_spatial_ref_sys (
            srs_name TEXT NOT NULL, srs_id INTEGER NOT NULL PRIMARY KEY,
            organization TEXT NOT NULL, organization_coordsys_id INTEGER NOT NULL,
            definition TEXT NOT NULL, description TEXT);
        """
    )
    from kart_tpu.crs import WGS84_WKT

    con.execute(
        "INSERT INTO gpkg_spatial_ref_sys VALUES ('WGS 84', 4326, 'EPSG', 4326, ?, NULL)",
        (WGS84_WKT,),
    )
    con.execute(
        "INSERT INTO gpkg_contents (table_name, data_type, identifier, srs_id) "
        f"VALUES ('{DS}', 'features', 'things', 4326)"
    )
    con.execute(
        f"INSERT INTO gpkg_geometry_columns VALUES ('{DS}', 'geom', 'POINT', 4326, 0, 0)"
    )
    con.execute(f"CREATE TABLE {DS} (id TEXT PRIMARY KEY NOT NULL, geom POINT, rating REAL)")
    for i, pk in enumerate(ids):
        con.execute(
            f"INSERT INTO {DS} VALUES (?, ?, ?)",
            (pk, gpkg_point(170.0 + i * 1e-3, -40.0 - i * 1e-3), i / 2.0),
        )
    con.commit()
    con.close()
    return path


def uuids(rng, n):
    return [str(uuid.UUID(bytes=rng.bytes(16))).upper() for _ in range(n)]


def import_repo(tmp_path, ids, monkeypatch, pipeline="0"):
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.importer import ImportSource
    from kart_tpu.importer.importer import import_sources

    monkeypatch.setattr(importer_mod, "SIDECAR_MIN_FEATURES", 5)
    monkeypatch.setenv("KART_IMPORT_PIPELINE", pipeline)
    gpkg = text_pk_gpkg(str(tmp_path / f"src-{pipeline}.gpkg"), ids)
    repo = KartRepo.init_repository(tmp_path / f"repo-{pipeline}")
    repo.config.set_many({"user.name": "T", "user.email": "t@example.com"})
    import_sources(repo, ImportSource.open(gpkg))
    return repo


def feature(pk, i, rating):
    return {"id": pk, "geom": Geometry.from_wkt(f"POINT ({i} {-i})"), "rating": rating}


MIXES = {
    "inserts": dict(n_ins=12, n_upd=0, n_del=0),
    "updates": dict(n_ins=0, n_upd=15, n_del=0),
    "deletes": dict(n_ins=0, n_upd=0, n_del=9),
    "all": dict(n_ins=7, n_upd=11, n_del=5),
}


def republish(repo, ids, rng, n_ins, n_upd, n_del, extra=()):
    """One commit of inserts (fresh UUIDs, then ``extra``), updates and
    deletes, uniform and disjoint; -> the inserted ids."""
    picked = rng.choice(len(ids), n_upd + n_del, replace=False)
    fresh = uuids(rng, n_ins) + list(extra)
    edit_commit(
        repo, DS,
        inserts=[feature(pk, 1000 + j, 0.5) for j, pk in enumerate(fresh)],
        updates=[feature(ids[i], int(i), 99.0) for i in picked[:n_upd]],
        deletes=[ids[i] for i in picked[n_upd:]],
    )
    return fresh


def cli_count(repo, env=None):
    from kart_tpu.cli import cli

    result = CliRunner().invoke(
        cli, ["-C", str(repo.workdir or repo.gitdir), "diff", "HEAD^...HEAD",
              "-o", "feature-count"], env=env, catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    text = result.output.strip()
    return int(text.split("\t")[-1].split()[0]) if text else 0


HOST = {"KART_DIFF_BACKEND": "host_native", "KART_DIFF_DEVICE": "0",
        "KART_DIFF_SHARDED": "0"}
DEVICE = {"KART_DIFF_DEVICE": "1", "KART_DIFF_SHARDED": "0"}


@pytest.fixture
def spans(tmp_path):
    """Counters on, and span events written where :func:`guard_spans` reads
    them: the CLI writes its trace file when a command closes."""
    tm.reset()
    tm.enable(metrics=True, trace=True, trace_path=str(tmp_path / "spans.json"))
    yield str(tmp_path / "spans.json")
    tm.reset()


def guard_spans(path):
    """The ``diff.hash_guard`` spans' attributes since the last call."""
    import json

    if not os.path.exists(path):
        return []
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    os.remove(path)
    return [
        {k: v for k, v in e["args"].items() if k in ("pairs", "collisions")}
        for e in events if e.get("name") == "diff.hash_guard"
    ]


def fallbacks():
    return {
        dict(labels)["why"]: v for (name, labels), v in tm.counters_snapshot().items()
        if name == "diff.hash_guard.fallbacks"
    }


# -- the fast count against the reference -------------------------------------------

@pytest.mark.parametrize("route", ["host", "device", "device_as_tpu"])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_hash_keyed_count_takes_the_columnar_route_and_equals_the_reference(
    tmp_path, monkeypatch, spans, mix, route
):
    rng = np.random.default_rng(abs(hash((mix, route))) % 2**32)
    ids = uuids(rng, 120)
    repo = import_repo(tmp_path, ids, monkeypatch)
    republish(repo, ids, rng, **MIXES[mix])
    assert all(
        sidecar.has_sidecar(repo, repo.structure(rev).datasets[DS])
        for rev in ("HEAD^", "HEAD")
    ), "the commit derives its sidecar"
    if route == "device_as_tpu":
        from kart_tpu import runtime
        from kart_tpu.ops import diff_kernel

        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
        monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", 1024)
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 28))

    def no_delta_path(*args, **kwargs):
        raise AssertionError("the count took the delta path")

    monkeypatch.setattr(engine, "get_feature_diff_columnar", no_delta_path)
    monkeypatch.setattr(engine, "get_feature_diff", no_delta_path)
    guard_spans(spans)
    got = cli_count(repo, HOST if route == "host" else DEVICE)
    assert got == reference_count(repo, "HEAD^", "HEAD") == sum(MIXES[mix].values())
    assert guard_spans(spans) == [{"pairs": MIXES[mix]["n_upd"], "collisions": 0}]
    assert fallbacks() == {}


# -- the guards, forced --------------------------------------------------------------

def test_within_side_collisions_fall_back_and_answer_exactly(tmp_path, monkeypatch, spans):
    """Four-bit keys: every revision holds a key twice. The sidecar says so
    when it is written; the count goes to the exact path."""
    monkeypatch.setattr(blocks, "KEY_BITS", 4)
    rng = np.random.default_rng(4)
    ids = uuids(rng, 60)
    repo = import_repo(tmp_path, ids, monkeypatch)
    republish(repo, ids, rng, n_ins=5, n_upd=6, n_del=4)
    head = repo.structure("HEAD").datasets[DS]
    if not sidecar.has_sidecar(repo, head):
        sidecar.build_sidecar(repo, head)  # derive declines: keys tell no row apart
    assert sidecar.load_block(repo, head).key_collisions is True
    assert cli_count(repo, HOST) == reference_count(repo, "HEAD^", "HEAD") == 15
    assert fallbacks().get("within", 0) >= 1
    assert guard_spans(spans) == []


def test_cross_version_collision_falls_back_and_answers_exactly(
    tmp_path, monkeypatch, spans
):
    """Sixteen-bit keys, no key twice in either revision, but a deleted pk
    and an inserted one share a key: the join reads them as one update; the
    guard finds two paths and the count goes to the exact path."""
    monkeypatch.setattr(blocks, "KEY_BITS", 16)
    rng = np.random.default_rng(16)

    def key(pk):
        return int(hash_feature_rows(msgpack_pk_rows([(pk,)]), ENC).keys[0])

    while True:
        ids = uuids(rng, 50)
        if len({key(pk) for pk in ids}) == len(ids):
            break
    victim = ids[7]
    twin = next(
        pk for pk in (f"TWIN-{i}" for i in range(1 << 20)) if key(pk) == key(victim)
    )
    repo = import_repo(tmp_path, ids, monkeypatch)
    edit_commit(repo, DS, inserts=[feature(twin, 1, 0.5)], deletes=[victim])
    head = repo.structure("HEAD").datasets[DS]
    if not sidecar.has_sidecar(repo, head):
        sidecar.build_sidecar(repo, head)
    assert sidecar.load_block(repo, head).key_collisions is False
    assert cli_count(repo, HOST) == reference_count(repo, "HEAD^", "HEAD") == 2
    assert fallbacks() == {"across": 2}  # the count route, then the delta path's
    assert [g["collisions"] for g in guard_spans(spans)] == [1, 1]


def test_a_key_equal_to_the_padding_key_falls_back_and_answers_exactly(
    tmp_path, monkeypatch, spans
):
    rng = np.random.default_rng(63)
    ids = uuids(rng, 40)
    padded = ENC.encode_filename((ids[3],)).encode()
    real = blocks.hash_keys

    def keys_with_one_pad(names):
        keys = real(names)
        keys[np.asarray([n == padded for n in names.tolist()], dtype=bool)] = blocks.PAD_KEY
        return keys

    monkeypatch.setattr(blocks, "hash_keys", keys_with_one_pad)
    repo = import_repo(tmp_path, ids, monkeypatch)
    republish(repo, ids, rng, n_ins=2, n_upd=3, n_del=2)
    assert cli_count(repo, HOST) == reference_count(repo, "HEAD^", "HEAD") == 7
    assert fallbacks().get("pad_key", 0) >= 1


# -- the tree writer ------------------------------------------------------------------

def _pk_rows(kind, rng, n):
    """Pk tuples of one kind, and what msgpack_pk_rows is handed for them."""
    if kind == "uuid":
        ids = uuids(rng, n)
        return [(s,) for s in ids], np.array([s.encode() for s in ids])
    if kind == "ragged":  # text of many lengths, some not ASCII
        ids = [("k" * int(rng.integers(1, 40))) + f"-{i}-é" * (i % 3) for i in range(n)]
        return [(s,) for s in ids], [(s,) for s in ids]
    pks = [(f"p{i % 97}", int(i)) for i in range(n)]  # a composite pk
    return pks, pks


@pytest.mark.parametrize("kind", ["uuid", "ragged", "composite"])
@pytest.mark.parametrize("n", [1, 37, 2500])
def test_writer_is_bit_identical_to_the_tree_builder(tmp_path, kind, n):
    from kart_tpu.core.repo import KartRepo

    odb = KartRepo.init_repository(tmp_path / "r").odb
    rng = np.random.default_rng(n)
    pks, given = _pk_rows(kind, rng, n)
    oids = rng.integers(0, 256, size=(n, 20), dtype=np.uint8)
    rows = hash_feature_rows(msgpack_pk_rows(given), ENC)
    paths = [ENC.encode_pks_to_path(pk) for pk in pks]
    assert rows.paths(ENC).tolist() == [p.encode() for p in paths]
    with odb.bulk_pack():
        whole = write_hash_feature_tree(odb, rows, oids, ENC)
    tb = TreeBuilder(odb, None)
    tb.insert_many(paths, [o.tobytes().hex() for o in oids])
    assert whole == tb.flush()

    # incremental: a tenth removed, a tenth rewritten, a tenth new
    gone = rng.choice(n, max(n // 10, 1), replace=False)
    rewritten = rng.choice(n, n // 10, replace=False)
    new_pks, new_given = _pk_rows(kind, np.random.default_rng(n + 1), n // 10 + 1)
    new_pks = [pk + ("new",) if kind == "composite" else pk for pk in new_pks]
    if kind == "composite":
        new_given = new_pks
    add_pks = [pks[i] for i in rewritten] + new_pks
    add_given = (
        np.concatenate([given[rewritten], new_given]) if kind == "uuid"
        else [given[i] for i in rewritten] + list(new_given)
    )
    add_oids = rng.integers(0, 256, size=(len(add_pks), 20), dtype=np.uint8)
    removed = hash_feature_rows(
        msgpack_pk_rows(given[gone] if kind == "uuid" else [given[i] for i in gone]), ENC
    )
    with odb.bulk_pack():
        after = write_hash_feature_tree(
            odb, hash_feature_rows(msgpack_pk_rows(add_given), ENC), add_oids, ENC,
            prev=whole, removed=removed,
        )
    tb = TreeBuilder(odb, whole)
    for i in gone:
        tb.remove(paths[i])
    tb.insert_many([ENC.encode_pks_to_path(pk) for pk in add_pks],
                   [o.tobytes().hex() for o in add_oids])
    assert after == tb.flush()


def test_writer_keeps_the_last_of_two_rows_and_empties_a_tree(tmp_path):
    from kart_tpu.core.repo import KartRepo

    odb = KartRepo.init_repository(tmp_path / "r").odb
    pks = ["A", "B", "A", "C", "B"]
    oids = np.arange(100, dtype=np.uint8).reshape(5, 20)
    rows = hash_feature_rows(msgpack_pk_rows([(p,) for p in pks]), ENC)
    assert rows.last_wins().tolist() == [2, 3, 4]
    root = write_hash_feature_tree(odb, rows, oids, ENC)
    tb = TreeBuilder(odb, None)
    tb.insert_many([ENC.encode_pks_to_path((p,)) for p in pks],
                   [o.tobytes().hex() for o in oids])
    assert root == tb.flush()
    empty = write_hash_feature_tree(
        odb, rows.take(slice(0, 0)), oids[:0], ENC, prev=root, removed=rows
    )
    assert empty == odb.write_tree([])
    assert write_hash_feature_tree(odb, rows.take(slice(0, 0)), oids[:0], ENC,
                                   prev=root) == root


def test_writer_declines_a_tree_it_did_not_lay_out(tmp_path):
    from kart_tpu.core.repo import KartRepo

    odb = KartRepo.init_repository(tmp_path / "r").odb
    tb = TreeBuilder(odb, None)
    tb.insert("AB/blob", "11" * 20)
    other = tb.flush()
    rows = hash_feature_rows(msgpack_pk_rows([("x",)]), ENC)
    assert write_hash_feature_tree(
        odb, rows, np.zeros((1, 20), np.uint8), ENC, prev=other
    ) is None


# -- the importer and the sidecar ------------------------------------------------------

def test_import_of_a_text_pk_gpkg_writes_the_tree_builders_tree(tmp_path, monkeypatch):
    """A serial and a pipelined import each write the tree a per-path builder
    writes for the same rows (each its own: an import names its schema's
    columns afresh, so the blobs differ); the sidecar holds its rows."""
    ids = uuids(np.random.default_rng(9), 300) + ["short", "a-longer-text-pk"]
    for pipeline in ("0", "force"):
        repo = import_repo(tmp_path, ids, monkeypatch, pipeline)
        ds = repo.structure("HEAD").datasets[DS]
        assert ds.path_encoder == ENC
        index = reference_index(repo, "HEAD")
        assert sorted(index) == sorted((pk,) for pk in ids)
        tb = TreeBuilder(repo.odb, None)
        tb.insert_many([ENC.encode_pks_to_path(pk) for pk in index], list(index.values()))
        assert ds.feature_tree.oid == tb.flush()
        block = sidecar.load_block(repo, ds, pad=False)
        got = {ds.decode_path_to_pks(block.paths[i]): oid for i, oid in enumerate(
            blocks.unpack_oid_hex(block.oids[: block.count]))}
        assert got == index and block.key_collisions is False


def _sidecar_columns(repo, ds):
    block = sidecar.load_block(repo, ds, pad=False)
    return (np.asarray(block.keys).tolist(), np.asarray(block.oids).tobytes(),
            block.paths.tolist(), np.asarray(block.envelopes).tobytes()
            if block.envelopes is not None else None, block.key_collisions)


def test_a_commit_derives_the_sidecar_a_rebuild_writes(tmp_path, monkeypatch):
    rng = np.random.default_rng(11)
    ids = uuids(rng, 200)
    repo = import_repo(tmp_path, ids, monkeypatch)
    republish(repo, ids, rng, n_ins=9, n_upd=14, n_del=6)
    head = repo.structure("HEAD").datasets[DS]
    derived = _sidecar_columns(repo, head)
    os.remove(sidecar.sidecar_file(repo, head.feature_tree.oid))
    sidecar.build_sidecar(repo, head)
    rebuilt = _sidecar_columns(repo, head)
    assert derived[:3] == rebuilt[:3] and derived[4] == rebuilt[4] is False


def test_path_offsets_widen_past_the_threshold(tmp_path, monkeypatch):
    """Past the threshold the offsets are written as uint64 and the header
    says so; under it, as before (uint32, no header key)."""
    import json

    from kart_tpu.core.repo import KartRepo
    from kart_tpu.ops.blocks import hash_keys_for_paths

    repo = KartRepo.init_repository(tmp_path / "r")
    paths = [ENC.encode_pks_to_path((f"pk-{i}",)) for i in range(50)]
    keys = hash_keys_for_paths(paths)
    oids = np.zeros((50, 20), np.uint8)

    class Dataset:
        class feature_tree:
            oid = "ab" * 20

        path_encoder = ENC

    for limit, width in ((1 << 32, None), (100, 8)):
        monkeypatch.setattr(sidecar, "PATH_OFFSETS_U32_MAX", limit)
        target = sidecar.save_sidecar(repo, Dataset.feature_tree.oid, keys, oids,
                                      paths=ByteRows.from_list([p.encode() for p in paths]))
        with open(target, "rb") as f:
            f.readline()
            header = json.loads(f.readline())
        assert header.get("path_offset_bytes") == width
        block = sidecar.load_block(repo, Dataset)
        assert block.paths.offs.dtype.itemsize == (width or 4)
        order = np.argsort(keys, kind="stable")
        assert [block.paths[i] for i in range(50)] == [paths[i] for i in order]
