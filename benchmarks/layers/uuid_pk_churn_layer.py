"""Layer builder ``uuid_pk_churn_layer``: a text-pk point layer, republished.

The benchmark's own generator for a dataset keyed by a text primary key: a
36-character upper-case UUID in ``id``, so the dataset takes Kart's
``msgpack/hash`` path scheme (64 branches, 4 levels: a feature's leaf tree
is named by the first four characters of ``b64(sha256(msgpack(pk)))``). It
writes a real Datasets-V3 repository through the program's own object store,
its column-wise msgpack/hash tree writer
(``kart_tpu.core.feature_tree.write_hash_feature_tree``) and its sidecar
writer, in two parts:

* :func:`build_base` — the import commit: ``rows`` features, row ``r``'s
  UUID drawn from a fixed generator (seed-free, so a checkout builds the
  base once and keeps it), its point where ``int_pk_layer`` puts pk
  ``PK_BASE + r``, ``rating = r / 2``.
* :func:`add_edit_commit` — from ``--seed``, one republish commit on
  ``refs/heads/churn``, a child of the import commit (``HEAD`` stays on the
  import commit, so the cell's command is ``kart diff HEAD...churn``):
  ``update_frac`` of the rows get ``rating = r / 2 + 0.25``,
  ``delete_frac`` are deleted, both uniform without replacement and
  disjoint; ``insert_frac`` rows are inserted with fresh UUIDs from the
  seed, which land at hash-uniform places in key order as every other row
  does. Its feature tree is the base tree with those leaves rewritten (the
  writer's incremental form) and its sidecar is derived from the base's
  (``sidecar.derive_sidecar``): what a commit does to a hash-keyed layer.

Every count is ``int(rows * frac)``, never drawn. A fresh UUID whose hash key
some row already holds, or another fresh one, is drawn again, so the two
revisions hold no two rows of one key (the cell is about the guard's cost,
not its fallback). The sidecars carry keys, oids, paths and the envelope
column; the vertex column is not written (configs list it under
``reduced``).
"""

import importlib.util
import json
import os
import struct

import numpy as np

DS_PATH = "layer"
BASE_SEED = 0x5EED_0042  # the base layer's UUIDs: not the run's --seed
_HEX = np.frombuffer(b"0123456789ABCDEF", dtype=np.uint8)
_GROUPS = ((0, 8), (9, 13), (14, 18), (19, 23), (24, 36))  # 8-4-4-4-12


def _sibling(name):
    """benchmarks/layers/<name>.py, loaded as run.py loads a builder."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_layers_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


int_layer = _sibling("int_pk_layer")  # origins, envelopes: the same points


def schema():
    """id text pk (a UUID), geom POINT EPSG:4326, rating float64."""
    from kart_tpu.models.schema import ColumnSchema, Schema

    return Schema(
        [
            ColumnSchema(
                id="c1b2c3d4-0001-4000-8000-000000000001", name="id",
                data_type="text", pk_index=0, extra_type_info={},
            ),
            ColumnSchema(
                id="c1b2c3d4-0002-4000-8000-000000000002", name="geom",
                data_type="geometry", pk_index=None,
                extra_type_info={"geometryType": "POINT", "geometryCRS": "EPSG:4326"},
            ),
            ColumnSchema(
                id="c1b2c3d4-0003-4000-8000-000000000003", name="rating",
                data_type="float", pk_index=None, extra_type_info={"size": 64},
            ),
        ]
    )


def uuids(rng, n):
    """``n`` random version-4 UUIDs, upper case, as a numpy ``S36`` array."""
    raw = rng.integers(0, 256, size=(n, 16), dtype=np.uint8)
    raw[:, 6] = (raw[:, 6] & 0x0F) | 0x40
    raw[:, 8] = (raw[:, 8] & 0x3F) | 0x80
    digits = np.empty((n, 32), dtype=np.uint8)
    digits[:, 0::2] = _HEX[raw >> 4]
    digits[:, 1::2] = _HEX[raw & 0x0F]
    out = np.full((n, 36), ord("-"), dtype=np.uint8)
    at = 0
    for lo, hi in _GROUPS:
        out[:, lo:hi] = digits[:, at : at + hi - lo]
        at += hi - lo
    return out.view("S36").reshape(n)


def old_rating(rows):
    return np.asarray(rows, dtype=np.float64) / 2.0


def new_rating(rows):
    return old_rating(rows) + 0.25


def blob_template():
    """One encoded feature blob of the schema and the byte offsets of its
    doubles (as ``int_pk_layer.blob_template``, which this schema's blobs
    share the layout of: the pk is in the path, not the blob). Checked
    against sentinel values, so a format change fails here."""
    from kart_tpu.geometry import Geometry

    x0, y0, rating = 10.0, 20.0, 1.5
    wkb = struct.pack("<BIdd", 1, 1, x0, y0)
    geom_off = 1 + 2 + 40 + 1 + 3  # 0x92, str8 legend hash, 0x92, ext8 header
    coords_off = geom_off + 8 + 5
    slots = [(coords_off, "<f8", "x0"), (coords_off + 8, "<f8", "y0"),
             (coords_off + 16 + 1, ">f8", "rating")]  # msgpack floats are BE
    _, blob = schema().encode_feature_blob(
        {"id": "0" * 36, "geom": Geometry.from_wkb(wkb), "rating": rating}
    )
    want = {"x0": x0, "y0": y0, "rating": rating}
    if len(blob) != coords_off + 25 or blob[geom_off : geom_off + 2] != b"GP":
        raise RuntimeError("feature blob layout is not the one this builder fills")
    for off, dtype, col in slots:
        if np.frombuffer(blob, dtype, 1, off)[0] != want[col]:
            raise RuntimeError(f"feature blob layout: no {col} at byte {off}")
    return np.frombuffer(blob, dtype=np.uint8), slots


def write_blobs(odb, rows, rating, layout_rows, chunk=1_000_000):
    """Blobs of layer rows ``rows`` (the point of row ``r`` is where
    ``int_pk_layer`` puts pk ``PK_BASE + r`` in a layer of ``layout_rows``);
    -> (n, 20) uint8 oids."""
    tmpl, slots = blob_template()
    out = np.empty((len(rows), 20), dtype=np.uint8)
    for i in range(0, len(rows), chunk):
        sl = slice(i, min(i + chunk, len(rows)))
        x0, y0 = int_layer.origins("POINT", int_layer.PK_BASE + rows[sl], layout_rows)
        cols = {"x0": x0, "y0": y0, "rating": rating[sl]}
        mat = np.tile(tmpl, (len(x0), 1))
        for off, dtype, col in slots:
            mat[:, off : off + 8] = (
                np.ascontiguousarray(cols[col], dtype=dtype).view(np.uint8).reshape(-1, 8)
            )
        out[sl] = odb.write_blobs_raw([row.tobytes() for row in mat])
    return out


def _envelopes(rows, layout_rows):
    return int_layer.envelopes(
        *int_layer.origins("POINT", int_layer.PK_BASE + rows, layout_rows)
    )


def _hash_rows(ids):
    from kart_tpu.models.paths import PathEncoder, hash_feature_rows, msgpack_pk_rows

    return hash_feature_rows(msgpack_pk_rows(ids), PathEncoder.GENERAL_ENCODER)


def build_base(dest, params):
    """Write the import commit into ``dest`` (an empty directory):
    ``dest/repo`` and the columns the edit commit starts from."""
    from kart_tpu.core.feature_tree import write_hash_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.crs import WGS84_WKT
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3
    from kart_tpu.models.paths import PathEncoder

    n = params["rows"]
    encoder = PathEncoder.GENERAL_ENCODER
    ids = uuids(np.random.default_rng(BASE_SEED), n)
    rows = np.arange(n, dtype=np.int64)
    hashed = _hash_rows(ids)
    if len(np.unique(hashed.keys)) != n:
        raise RuntimeError("two base rows share a hash key: change BASE_SEED")
    repo = KartRepo.init_repository(os.path.join(dest, "repo"))
    repo.config.set_many({"user.name": "Bench", "user.email": "bench@example.com"})
    odb = repo.odb
    with odb.bulk_pack(level=0):
        oids = write_blobs(odb, rows, old_rating(rows), n)
    with odb.bulk_pack(level=0):
        ftree = write_hash_feature_tree(odb, hashed, oids, encoder)
        tb = TreeBuilder(odb, None)
        for blob_path, data in Dataset3.new_dataset_meta_blobs(
            DS_PATH, schema(), title="benchmark text-pk layer",
            crs_defs={"EPSG:4326": WGS84_WKT}, path_encoder=encoder,
        ):
            tb.insert(blob_path, odb.write_blob(data))
        tb.insert(f"{DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree, mode=MODE_TREE)
        root = tb.flush()
    commit = repo.create_commit("HEAD", root, "import", [])
    kcol = sidecar.save_sidecar(
        repo, ftree, hashed.keys, oids, paths=hashed.paths(encoder),
        envelopes=_envelopes(rows, n),
    )
    np.save(os.path.join(dest, "ids.npy"), ids)
    np.save(os.path.join(dest, "oids.npy"), oids)
    with open(os.path.join(dest, "base.json"), "w") as f:
        json.dump({"commit": commit, "root": root, "feature_tree": ftree,
                   "sidecar": os.path.basename(kcol),
                   "branch": repo.refs.head_branch()}, f)


def _count(params, key):
    return int(params["rows"] * params[key])


def edit_sets(params, seed, base_keys):
    """(updated rows, deleted rows, the inserted rows' UUIDs) of ``seed``:
    row numbers of the base layer, sorted. ``base_keys``: the base's sorted
    hash keys, which no inserted UUID's key may equal."""
    rng = np.random.default_rng(seed)
    n_upd, n_del, n_ins = (_count(params, k) for k in ("update_frac", "delete_frac",
                                                        "insert_frac"))
    picked = rng.choice(params["rows"], size=n_upd + n_del, replace=False)
    fresh = uuids(rng, n_ins)
    while True:
        keys = _hash_rows(fresh).keys
        pos = np.minimum(np.searchsorted(base_keys, keys), len(base_keys) - 1)
        _, first = np.unique(keys, return_index=True)
        bad = base_keys[pos] == keys
        bad[np.setdiff1d(np.arange(n_ins), first)] = True
        if not bad.any():
            break
        fresh[bad] = uuids(rng, int(bad.sum()))
    return np.sort(picked[:n_upd]), np.sort(picked[n_upd:]), fresh


def add_edit_commit(base, work, params, seed):
    """The run's repository: a thin one at ``work/repo`` over the base's
    objects, ``HEAD`` on the import commit, the republish commit of ``seed``
    on ``refs/heads/churn``. -> (repo path, {"commits": {"churn": {"commit",
    "rows", "updated_ids", "deleted_ids", "inserted_ids", "n_edits"}},
    "n_edits", "path_structure": the dataset's path-structure.json as the
    commit holds it})."""
    from kart_tpu.core.feature_tree import write_hash_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3
    from kart_tpu.models.paths import PathEncoder

    with open(os.path.join(base, "base.json")) as f:
        meta = json.load(f)
    base_git = os.path.join(os.path.abspath(base), "repo", ".kart")
    path = os.path.join(work, "repo")
    repo = KartRepo.init_repository(path)
    repo.config.set_many({"user.name": "Bench", "user.email": "bench@example.com"})
    repo.odb.add_alternate(os.path.join(base_git, "objects"))
    repo.refs.set(meta["branch"], meta["commit"], "branch: base layer")
    columnar = os.path.join(repo.gitdir, "columnar")
    os.makedirs(columnar, exist_ok=True)
    os.symlink(os.path.join(base_git, "columnar", meta["sidecar"]),
               os.path.join(columnar, meta["sidecar"]))

    encoder = PathEncoder.GENERAL_ENCODER
    n = params["rows"]
    base_ds = repo.structure(meta["commit"]).datasets[DS_PATH]
    base_block = sidecar.load_block(repo, base_ds, pad=False)
    updated, deleted, fresh = edit_sets(params, seed, np.asarray(base_block.keys))
    ids = np.load(os.path.join(base, "ids.npy"), mmap_mode="r")
    n_ins = len(fresh)
    inserted_rows = n + np.arange(n_ins, dtype=np.int64)
    odb = repo.odb
    with odb.bulk_pack(level=0):
        # the inserted points lie where the layer, grown by them, puts them
        add_oids = np.concatenate([
            write_blobs(odb, updated, new_rating(updated), n),
            write_blobs(odb, inserted_rows, old_rating(inserted_rows), n + n_ins),
        ])
        added = _hash_rows(np.concatenate([ids[updated], fresh]))
        removed = _hash_rows(ids[deleted])
        ftree = write_hash_feature_tree(
            odb, added, add_oids, encoder, prev=meta["feature_tree"], removed=removed
        )
        tb = TreeBuilder(odb, meta["root"])
        tb.insert(f"{DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree, mode=MODE_TREE)
        root = tb.flush()
    commit = repo.create_commit("refs/heads/churn", root, "republish: churn",
                                [meta["commit"]])
    add_paths = [p.decode() for p in added.paths(encoder).tolist()]
    add_envs = np.concatenate([_envelopes(updated, n), _envelopes(inserted_rows, n + n_ins)])
    sidecar.derive_sidecar(
        repo, base_block, ftree,
        [p.decode() for p in removed.paths(encoder).tolist()],
        dict(zip(add_paths, (o.tobytes().hex() for o in add_oids))),
        dict(zip(add_paths, add_envs.tolist())),
    )
    structure = repo.odb.tree(root).get_or_none(
        f"{DS_PATH}/{Dataset3.DATASET_DIRNAME}/{Dataset3.PATH_STRUCTURE_PATH}"
    )
    churn = {
        "commit": commit,
        "rows": n - len(deleted) + n_ins,
        "updated_ids": ids[updated],
        "deleted_ids": ids[deleted],
        "inserted_ids": fresh,
        "n_edits": len(updated) + len(deleted) + n_ins,
    }
    return path, {
        "commits": {"churn": churn},
        "n_edits": churn["n_edits"],
        "path_structure": json.loads(repo.odb.read_blob(structure.oid)),
    }
