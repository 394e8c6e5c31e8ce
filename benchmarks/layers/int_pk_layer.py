"""Layer builder ``int_pk_layer``: one int-pk dataset, every blob in the pack.

The benchmark's own generator (after ``kart_tpu/synth.py``, which later PRs
may change; this copy they may not). It writes a real Datasets-V3 repository
through the program's own object store, tree builder and sidecar writer, in
two parts:

* :func:`build_base` — the import commit: ``rows`` features, pk
  ``PK_BASE + i``, ``rating = pk / 2``, geometry a function of the pk. It
  does not depend on the seed, so a checkout builds it once and keeps it.
* :func:`add_edit_commit` — the edit commit, made anew in every run from
  ``--seed``: ``edit_frac`` of the rows, chosen uniformly without
  replacement, get ``rating = pk``. It is written into a thin repository in
  the run's own directory that borrows the base's objects through
  ``objects/info/alternates``, so the cached base is never written to.

Every feature blob has one fixed layout per geometry type, so a column of
blobs is a tiled template with the doubles filled in, not a per-feature
encode. The sidecars carry keys and oids, and for points the envelope
column; the vertex column is not written (configs list it under
``reduced``).
"""

import json
import os
import struct

import numpy as np

PK_BASE = 1 << 24  # keeps every feature path the same width (uint32 msgpack)
DS_PATH = "layer"
BOX = 0.001  # degrees: side of a polygon, and of a point's envelope

_GEOM_OFF = 1 + 2 + 40 + 1 + 3  # 0x92, str8 legend hash, 0x92, ext8 header
_GPKG_HEADER = 8  # magic, version, flags, srid


def schema_for(geometry):
    """fid int64 pk, geom <geometry> EPSG:4326, rating float64."""
    from kart_tpu.models.schema import ColumnSchema, Schema

    tag = {"POINT": "a", "POLYGON": "b"}[geometry]
    return Schema(
        [
            ColumnSchema(
                id=f"{tag}1b2c3d4-0001-4000-8000-000000000001", name="fid",
                data_type="integer", pk_index=0, extra_type_info={"size": 64},
            ),
            ColumnSchema(
                id=f"{tag}1b2c3d4-0002-4000-8000-000000000002", name="geom",
                data_type="geometry", pk_index=None,
                extra_type_info={
                    "geometryType": geometry, "geometryCRS": "EPSG:4326",
                },
            ),
            ColumnSchema(
                id=f"{tag}1b2c3d4-0003-4000-8000-000000000003", name="rating",
                data_type="float", pk_index=None, extra_type_info={"size": 64},
            ),
        ]
    )


def old_rating(pks):
    return np.asarray(pks, dtype=np.float64) / 2.0


def new_rating(pks):
    return np.asarray(pks, dtype=np.float64)


def origins(geometry, pks, rows):
    """-> (x0, y0) float64 per pk. Points lie as an OSM-nodes import lays
    them: consecutive pks sweep longitude inside a latitude band, bands
    stack south to north, with a golden-ratio jitter in each band. Polygon
    origins are spread over the globe on a 0.01-degree lattice."""
    pks = np.asarray(pks, dtype=np.int64)
    if geometry == "POLYGON":
        x0 = (pks % 35900) / 100.0 - 179.5
        y0 = ((pks // 359) % 16800) / 100.0 - 84.0
        return x0.astype(np.float64), y0.astype(np.float64)
    idx = (pks - PK_BASE).astype(np.float64)
    span = max(float(rows), 1.0)
    n_bands = max(1, int(round((span / 4096.0) ** 0.5)))
    per_band = span / n_bands
    band = np.minimum(np.floor(idx / per_band), n_bands - 1)
    lon = -180.0 + 360.0 * (idx - band * per_band) / per_band
    band_h = 170.0 / n_bands
    jitter = (np.mod(idx * 0.6180339887498949, 1.0) - 0.5) * (band_h * 0.9)
    lat = -85.0 + band_h * (band + 0.5) + jitter
    # the envelope column is float32: the point sits on its float32 corner
    return (
        lon.astype(np.float32).astype(np.float64),
        lat.astype(np.float32).astype(np.float64),
    )


def envelopes(x0, y0):
    """wsen float32 (N, 4): the point's (or box's) corner plus BOX."""
    out = np.empty((len(x0), 4), dtype=np.float32)
    out[:, 0] = x0
    out[:, 1] = y0
    out[:, 2] = x0 + BOX
    out[:, 3] = y0 + BOX
    return out


def blob_template(geometry):
    """One encoded feature blob of the layer's schema and the byte offsets
    of its doubles: -> (uint8 template, [(offset, dtype, column)]), columns
    named x0 y0 x1 y1 rating. Offsets are derived from the format and
    checked against sentinel values, so a format change fails here and
    cannot write corrupt blobs."""
    from kart_tpu.geometry import Geometry

    x0, y0, rating = 10.0, 20.0, 1.5
    x1, y1 = x0 + BOX, y0 + BOX
    if geometry == "POINT":
        wkb = struct.pack("<BIdd", 1, 1, x0, y0)
        coords_off = _GEOM_OFF + _GPKG_HEADER + 5
        slots = [(coords_off, "<f8", "x0"), (coords_off + 8, "<f8", "y0")]
        rating_off = coords_off + 16 + 1
    else:
        ring = ("x0", "y0", "x1", "y0", "x1", "y1", "x0", "y1", "x0", "y0")
        at = {"x0": x0, "y0": y0, "x1": x1, "y1": y1}
        wkb = struct.pack("<BIII", 1, 3, 1, 5) + b"".join(
            struct.pack("<d", at[c]) for c in ring
        )
        env_off = _GEOM_OFF + _GPKG_HEADER  # minx maxx miny maxy
        coords_off = env_off + 32 + 13
        slots = [
            (env_off + 8 * k, "<f8", c)
            for k, c in enumerate(("x0", "x1", "y0", "y1"))
        ] + [(coords_off + 8 * k, "<f8", c) for k, c in enumerate(ring)]
        rating_off = coords_off + 80 + 1
    slots.append((rating_off, ">f8", "rating"))  # msgpack floats are BE
    _, blob = schema_for(geometry).encode_feature_blob(
        {"fid": 1, "geom": Geometry.from_wkb(wkb), "rating": rating}
    )
    want = {"x0": x0, "y0": y0, "x1": x1, "y1": y1, "rating": rating}
    if len(blob) != rating_off + 8 or blob[_GEOM_OFF:_GEOM_OFF + 2] != b"GP":
        raise RuntimeError("feature blob layout is not the one this builder fills")
    for off, dtype, col in slots:
        if np.frombuffer(blob, dtype, 1, off)[0] != want[col]:
            raise RuntimeError(f"feature blob layout: no {col} at byte {off}")
    return np.frombuffer(blob, dtype=np.uint8), slots


def write_blobs(odb, geometry, pks, rating, rows, chunk=1_000_000):
    """Columnar blob build and batch pack write; -> (n, 20) uint8 oids."""
    tmpl, slots = blob_template(geometry)
    out = np.empty((len(pks), 20), dtype=np.uint8)
    for i in range(0, len(pks), chunk):
        sl = slice(i, min(i + chunk, len(pks)))
        x0, y0 = origins(geometry, pks[sl], rows)
        cols = {"x0": x0, "y0": y0, "x1": x0 + BOX, "y1": y0 + BOX,
                "rating": rating[sl]}
        mat = np.tile(tmpl, (len(x0), 1))
        for off, dtype, col in slots:
            mat[:, off:off + 8] = (
                np.ascontiguousarray(cols[col], dtype=dtype)
                .view(np.uint8).reshape(len(x0), 8)
            )
        out[sl] = odb.write_blobs_raw([row.tobytes() for row in mat])
    return out


def _envelope_column(params, pks):
    if not params.get("envelopes"):
        return None
    return envelopes(*origins(params["geometry"], pks, params["rows"]))


def _pks(params):
    return np.arange(PK_BASE, PK_BASE + params["rows"], dtype=np.int64)


def build_base(dest, params):
    """Write the import commit of the layer into ``dest`` (an empty
    directory): ``dest/repo`` and the columns the edit commit starts from."""
    from kart_tpu.core.feature_tree import emit_feature_tree, plan_int_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.crs import WGS84_WKT
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3
    from kart_tpu.models.paths import PathEncoder

    geometry = params["geometry"]
    pks = _pks(params)
    repo = KartRepo.init_repository(os.path.join(dest, "repo"))
    repo.config.set_many({"user.name": "Bench", "user.email": "bench@example.com"})
    odb = repo.odb
    with odb.bulk_pack(level=0):
        oids = write_blobs(odb, geometry, pks, old_rating(pks), params["rows"])
    plan = plan_int_feature_tree(pks)
    with odb.bulk_pack(level=0):
        ftree, leaf_oids = emit_feature_tree(odb, plan, oids)
        tb = TreeBuilder(odb, None)
        for blob_path, data in Dataset3.new_dataset_meta_blobs(
            DS_PATH, schema_for(geometry), title="benchmark layer",
            crs_defs={"EPSG:4326": WGS84_WKT},
            path_encoder=PathEncoder.INT_PK_ENCODER,
        ):
            tb.insert(blob_path, odb.write_blob(data))
        tb.insert(f"{DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree,
                  mode=MODE_TREE)
        root = tb.flush()
    commit = repo.create_commit("HEAD", root, "import", [])
    kcol = sidecar.save_sidecar(
        repo, ftree, pks, oids, envelopes=_envelope_column(params, pks)
    )
    np.save(os.path.join(dest, "oids.npy"), oids)
    np.save(os.path.join(dest, "leaf_oids.npy"), np.asarray(leaf_oids, dtype="S40"))
    with open(os.path.join(dest, "base.json"), "w") as f:
        json.dump({"commit": commit, "root": root, "sidecar": os.path.basename(kcol),
                   "branch": repo.refs.head_branch()}, f)


def add_edit_commit(base, work, params, seed):
    """The run's repository: a thin one at ``work/repo`` over the base's
    objects, with the edit commit of ``seed`` on top.
    -> (repo path, {"edit_pks": sorted int64, "n_edits": int})."""
    from kart_tpu.core.feature_tree import emit_feature_tree, plan_int_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3

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

    n = params["rows"]
    pks = _pks(params)
    n_edits = max(1, int(n * params["edit_frac"]))
    rows = np.sort(np.random.default_rng(seed).choice(n, size=n_edits, replace=False))
    oids = np.load(os.path.join(base, "oids.npy"))
    leaf_oids = [s.decode() for s in np.load(os.path.join(base, "leaf_oids.npy"))]
    odb = repo.odb
    with odb.bulk_pack(level=0):
        oids[rows] = write_blobs(
            odb, params["geometry"], pks[rows], new_rating(pks[rows]), n
        )
        ftree, _ = emit_feature_tree(
            odb, plan_int_feature_tree(pks), oids, prev=(leaf_oids, rows)
        )
        tb = TreeBuilder(odb, meta["root"])
        tb.insert(f"{DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree,
                  mode=MODE_TREE)
        root = tb.flush()
    repo.create_commit("HEAD", root, "edit", [meta["commit"]])
    sidecar.save_sidecar(repo, ftree, pks, oids,
                         envelopes=_envelope_column(params, pks))
    return path, {"edit_pks": pks[rows], "n_edits": n_edits}
