"""Synthetic repo generation at benchmark scale.

Builds a real kart_tpu repository — packs, Merkle feature trees, commits,
refs, columnar sidecars — directly from generated (pk, oid) columns, so the
100M-feature north-star configs (BASELINE.json) can be measured end-to-end
through the CLI without paying a full import (the reference's equivalent
scaffolding is its synthetic pytest-benchmark layers,
tests/test_structure.py:106-165).

Two blob modes:

* ``blobs="real"``   — every feature blob is written to the pack; the repo
  is fully self-contained (used by tests to prove the vectorized tree
  builder is bit-identical to a real import).
* ``blobs="promised"`` — only trees/meta/commits are written; feature blob
  oids exist in trees + sidecars but the blobs themselves are absent, the
  same state a spatially-filtered partial clone leaves a repo in
  (tri-state ODB: present/absent/promised). Diff classification — the
  measured path — reads only (pk, oid) columns, never blob contents.

The feature-tree builder is fully vectorized: filenames come from the
PathEncoder's batch matrix, per-leaf payloads are sliced from one entries
buffer, and tree objects are hashed+deflated through the native batch IO.
"""

import numpy as np

from kart_tpu.core.objects import MODE_TREE
from kart_tpu.core.tree_builder import TreeBuilder
from kart_tpu.core.feature_tree import (  # noqa: F401 - re-exported API
    TreePlan,
    build_int_feature_tree,
    emit_feature_tree,
    plan_int_feature_tree,
)
from kart_tpu.models.paths import PathEncoder
from kart_tpu.models.schema import ColumnSchema, Schema

SYNTH_SCHEMA = Schema(
    [
        ColumnSchema(
            id="a1b2c3d4-0001-4000-8000-000000000001",
            name="fid",
            data_type="integer",
            pk_index=0,
            extra_type_info={"size": 64},
        ),
        ColumnSchema(
            id="a1b2c3d4-0002-4000-8000-000000000002",
            name="rating",
            data_type="float",
            pk_index=None,
            extra_type_info={"size": 64},
        ),
    ]
)


SYNTH_SPATIAL_SCHEMA = Schema(
    [
        ColumnSchema(
            id="a1b2c3d4-0001-4000-8000-000000000001",
            name="fid",
            data_type="integer",
            pk_index=0,
            extra_type_info={"size": 64},
        ),
        ColumnSchema(
            id="a1b2c3d4-0004-4000-8000-000000000004",
            name="geom",
            data_type="geometry",
            pk_index=None,
            extra_type_info={
                "geometryType": "POINT",
                "geometryCRS": "EPSG:4326",
            },
        ),
        ColumnSchema(
            id="a1b2c3d4-0002-4000-8000-000000000002",
            name="rating",
            data_type="float",
            pk_index=None,
            extra_type_info={"size": 64},
        ),
    ]
)


def synth_feature_blob(pk):
    """The (deterministic) feature blob content for pk in 'real' mode."""
    return SYNTH_SCHEMA.encode_feature_blob({"fid": int(pk), "rating": pk / 2.0})[1]


def _synth_oids(pks, seed):
    """Deterministic pseudo-random blob oids for 'promised' mode."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(len(pks), 20), dtype=np.uint8)


def _real_oids(odb, pks, batch=1_000_000):
    """'real' mode: write every feature blob; -> its oid column."""
    out = np.empty((len(pks), 20), dtype=np.uint8)
    for i in range(0, len(pks), batch):
        chunk = pks[i : i + batch]
        contents = [synth_feature_blob(pk) for pk in chunk.tolist()]
        hexes = odb.write_blobs(contents)
        out[i : i + len(chunk)] = np.frombuffer(
            bytes.fromhex("".join(hexes)), dtype=np.uint8
        ).reshape(-1, 20)
    return out


def synth_envelopes(pks, span=None, base=None):
    """Deterministic per-pk wsen EPSG:4326 envelopes (float32 (N,4)): small
    boxes laid out like a real OSM-nodes import — consecutive pks sweep
    longitude within a latitude band, bands stack south-to-north, with a
    golden-ratio lat jitter inside each band. The layout covers the globe
    (a w,s,e,n rectangle query still selects ~(area fraction) of the
    features) while keeping pk-contiguous runs spatially tight, the
    locality real node-id assignment exhibits and the sidecar's block
    aggregates exist to exploit. ``span``/``base`` describe the full pk
    range (default: inferred from ``pks``) — pass both when generating a
    subset so its rows land exactly where full-set generation puts them."""
    pks = np.asarray(pks, dtype=np.int64)
    if not len(pks):
        return np.empty((0, 4), dtype=np.float32)
    if base is None:
        base = int(pks.min())
    idx = (pks - base).astype(np.float64)
    if span is None:
        span = float(idx.max()) + 1.0
    span = max(float(span), 1.0)
    n_bands = max(1, int(round((span / 4096.0) ** 0.5)))
    rows_per_band = span / n_bands
    band = np.minimum(np.floor(idx / rows_per_band), n_bands - 1)
    lon = -180.0 + 360.0 * (idx - band * rows_per_band) / rows_per_band
    band_h = 170.0 / n_bands
    jitter = (np.mod(idx * 0.6180339887498949, 1.0) - 0.5) * (band_h * 0.9)
    lat = -85.0 + band_h * (band + 0.5) + jitter
    out = np.empty((len(pks), 4), dtype=np.float32)
    out[:, 0] = lon
    out[:, 1] = lat
    out[:, 2] = lon + 0.001
    out[:, 3] = lat + 0.001
    return out


def _changed_row_oids(odb, sel_pks, ratings, schema, geom_xy=None,
                      batch=200_000):
    """Write real feature blobs for a selection of rows; -> (n, 20) oids.
    geom_xy: optional (lon, lat) column pair for spatial schemas."""
    import struct

    from kart_tpu.geometry import Geometry

    out = np.empty((len(sel_pks), 20), dtype=np.uint8)
    for i in range(0, len(sel_pks), batch):
        sl = slice(i, min(i + batch, len(sel_pks)))
        contents = []
        if geom_xy is None:
            for pk, r in zip(sel_pks[sl].tolist(), ratings[sl].tolist()):
                contents.append(
                    schema.encode_feature_blob({"fid": pk, "rating": r})[1]
                )
        else:
            xs, ys = geom_xy
            for pk, r, x, y in zip(
                sel_pks[sl].tolist(), ratings[sl].tolist(),
                xs[sl].tolist(), ys[sl].tolist(),
            ):
                geom = Geometry.from_wkb(struct.pack("<BIdd", 1, 1, x, y))
                contents.append(
                    schema.encode_feature_blob(
                        {"fid": pk, "geom": geom, "rating": r}
                    )[1]
                )
        out[sl] = odb.write_blobs_raw(contents)
    return out


def commit_feature_edits(repo, ds_path, *, inserts=(), updates=(), deletes=(),
                         message="edit features", ref="HEAD"):
    """Build and commit a small feature diff against ``ref``; -> commit
    oid. The one fixture-edit helper behind both the test suite
    (tests/helpers.edit_commit) and bench.py's merge-storm writers — the
    diff-construction idiom lives here so the two can't drift."""
    from kart_tpu.diff.structs import (
        DatasetDiff,
        Delta,
        DeltaDiff,
        KeyValue,
        RepoDiff,
    )

    structure = repo.structure(ref)
    ds = structure.datasets[ds_path]
    pk_col = ds.schema.pk_columns[0].name
    feature_diff = DeltaDiff()
    for f in inserts:
        feature_diff.add_delta(Delta.insert(KeyValue((f[pk_col], f))))
    for f in updates:
        old = ds.get_feature([f[pk_col]])
        feature_diff.add_delta(
            Delta.update(KeyValue((f[pk_col], old)), KeyValue((f[pk_col], f)))
        )
    for pk in deletes:
        old = ds.get_feature([pk])
        feature_diff.add_delta(Delta.delete(KeyValue((pk, old))))
    ds_diff = DatasetDiff()
    ds_diff["feature"] = feature_diff
    repo_diff = RepoDiff()
    repo_diff[ds_path] = ds_diff
    return structure.commit_diff(repo_diff, message)


def synth_repo(path, n, *, edit_frac=0.01, seed=0, blobs="promised",
               ds_path="synth", spatial=False):
    """Create a repo at ``path`` with one int-pk dataset of ``n`` features
    and two commits: the base import and an ``edit_frac`` oid-rewrite.
    -> (repo, dict with commit oids, edit count and the edited pks).

    Blob modes: "real" writes every feature blob; "promised" writes none
    (partial-clone state); "changed" writes real blobs for the edited rows
    only, in both revisions — exactly the set a full-output diff
    materialises, at 1/100th of the blob-write cost at 1% edit fraction.

    spatial=True adds a geometry column to the schema and writes
    per-feature envelope columns (:func:`synth_envelopes`) into the
    sidecars — the spatially-filtered diff's prefilter input (BASELINE
    config #4)."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3

    repo = KartRepo.init_repository(path)
    repo.config.set_many(
        {"user.name": "Synth", "user.email": "synth@example.com"}
    )
    odb = repo.odb

    base = 1 << 24  # keeps every filename the same width (uint32 msgpack)
    pks = np.arange(base, base + n, dtype=np.int64)

    schema = SYNTH_SCHEMA
    crs_defs = None
    envelopes = None
    vertices = None
    if spatial:
        assert blobs in ("promised", "changed"), (
            "spatial synth supports promised/changed blobs only"
        )
        schema = SYNTH_SPATIAL_SCHEMA
        from kart_tpu.epsg import epsg_wkt
        from kart_tpu.geom import boxes_vertex_column

        crs_defs = {"EPSG:4326": epsg_wkt(4326)}
        envelopes = synth_envelopes(pks)
        # real vertex columns without a blob walk: each synthetic feature's
        # geometry IS its envelope box, so the exact-refine lane has actual
        # polygons to chew on at bench scale (docs/FORMAT.md §3.4)
        vertices = boxes_vertex_column(envelopes)

    if blobs == "real":
        with odb.bulk_pack(level=0):
            oids1 = _real_oids(odb, pks)
    else:
        oids1 = _synth_oids(pks, seed)

    n_edits = max(1, int(n * edit_frac)) if edit_frac else 0
    rng = np.random.default_rng(seed + 1)
    edit_rows = rng.choice(n, size=n_edits, replace=False) if n_edits else np.zeros(0, np.int64)
    oids2 = oids1.copy()
    if n_edits:
        if blobs == "real":
            # edited features get a different (deterministic) rating
            contents = [
                SYNTH_SCHEMA.encode_feature_blob(
                    {"fid": int(pks[r]), "rating": float(pks[r])}
                )[1]
                for r in edit_rows.tolist()
            ]
            with odb.bulk_pack(level=0):
                hexes = odb.write_blobs(contents)
            oids2[edit_rows] = np.frombuffer(
                bytes.fromhex("".join(hexes)), dtype=np.uint8
            ).reshape(-1, 20)
        elif blobs == "changed":
            sel = pks[edit_rows]
            geom_xy = None
            if envelopes is not None:
                geom_xy = (
                    envelopes[edit_rows, 0].astype(np.float64),
                    envelopes[edit_rows, 1].astype(np.float64),
                )
            with odb.bulk_pack(level=0):
                oids1[edit_rows] = _changed_row_oids(
                    odb, sel, sel / 2.0, schema, geom_xy
                )
                oids2[edit_rows] = _changed_row_oids(
                    odb, sel, sel.astype(np.float64), schema, geom_xy
                )
        else:
            oids2[edit_rows] = _synth_oids(edit_rows, seed + 2)

    plan = plan_int_feature_tree(pks)
    commits = []
    prev = None
    for oids_u8, message in ((oids1, "synth import"), (oids2, "synth edits")):
        with odb.bulk_pack(level=0):
            ftree, leaf_oids = emit_feature_tree(odb, plan, oids_u8, prev=prev)
            prev = (leaf_oids, edit_rows)
            tb = TreeBuilder(odb, repo.head_tree_oid if commits else None)
            for blob_path, data in Dataset3.new_dataset_meta_blobs(
                ds_path,
                schema,
                title="synthetic benchmark layer",
                crs_defs=crs_defs,
                path_encoder=PathEncoder.INT_PK_ENCODER,
            ):
                tb.insert(blob_path, odb.write_blob(data))
            tb.insert(
                f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature", ftree, mode=MODE_TREE
            )
            root = tb.flush()
        commit_oid = repo.create_commit(
            "HEAD", root, message, [commits[-1]] if commits else []
        )
        commits.append(commit_oid)
        sidecar.save_sidecar(
            repo, ftree, pks, oids_u8, envelopes=envelopes, vertices=vertices
        )

    return repo, {
        "base_commit": commits[0],
        "edit_commit": commits[1],
        "n": n,
        "n_edits": n_edits,
        # the edited pks, sorted — what a diff of the two commits must name
        "edit_pks": np.sort(pks[edit_rows]),
    }


# -- polygon layer (BASELINE config #3) -------------------------------------

POLY_SCHEMA = Schema(
    [
        ColumnSchema(
            id="b1b2c3d4-0001-4000-8000-000000000001",
            name="fid",
            data_type="integer",
            pk_index=0,
            extra_type_info={"size": 64},
        ),
        ColumnSchema(
            id="b1b2c3d4-0002-4000-8000-000000000002",
            name="geom",
            data_type="geometry",
            pk_index=None,
            extra_type_info={
                "geometryType": "POLYGON",
                "geometryCRS": "EPSG:4326",
            },
        ),
        ColumnSchema(
            id="b1b2c3d4-0003-4000-8000-000000000003",
            name="rating",
            data_type="float",
            pk_index=None,
            extra_type_info={"size": 64},
        ),
    ]
)


def _poly_blob_template():
    """One real encoded polygon feature blob + the byte offsets of its
    variable fields. Every synthetic polygon blob has the same fixed layout
    (5-point ring, one ring, XY envelope), so the 10M-blob build is a
    columnar fill of a tiled template instead of 10M per-feature encodes.
    Offsets are derived structurally and asserted against the template, so
    a format change breaks loudly here rather than corrupting blobs."""
    import struct

    from kart_tpu.geometry import Geometry

    x0, y0, d = 10.0, 20.0, 0.001
    ring = [(x0, y0), (x0 + d, y0), (x0 + d, y0 + d), (x0, y0 + d), (x0, y0)]
    wkb = (
        struct.pack("<BIII", 1, 3, 1, len(ring))
        + b"".join(struct.pack("<2d", *p) for p in ring)
    )
    _, blob = POLY_SCHEMA.encode_feature_blob(
        {"fid": 1, "geom": Geometry.from_wkb(wkb), "rating": 1.5}
    )
    # msgpack layout: 0x92, str8(40-char legend hash), 0x92,
    # ext8(type G, 133B geometry), 0xcb + float64 rating
    geom_off = 1 + 2 + 40 + 1 + 3
    env_off = geom_off + 8  # GPKG header: magic+ver+flags+srid
    coords_off = env_off + 32 + 13  # envelope, then wkb head (1+4+4+4)
    rating_off = coords_off + 80 + 1  # 10 ring doubles, 0xcb marker
    assert blob[0] == 0x92 and blob[geom_off - 3] == 0xC7
    assert blob[geom_off : geom_off + 2] == b"GP"
    assert blob[rating_off - 1] == 0xCB
    assert len(blob) == rating_off + 8
    assert struct.unpack_from("<d", blob, env_off)[0] == x0  # minx
    assert struct.unpack_from("<d", blob, coords_off)[0] == x0
    assert struct.unpack_from(">d", blob, rating_off)[0] == 1.5
    return np.frombuffer(blob, dtype=np.uint8), env_off, coords_off, rating_off


def _poly_xy(pks):
    """Deterministic polygon origins spread over the globe."""
    x0 = (pks % 35900) / 100.0 - 179.5
    y0 = ((pks // 359) % 16800) / 100.0 - 84.0
    return x0.astype(np.float64), y0.astype(np.float64)


def _write_poly_blobs(odb, pks, rating, chunk=1_000_000):
    """Vectorized polygon blob build + batch pack write; -> (n, 20) oids."""
    tmpl, env_off, coords_off, rating_off = _poly_blob_template()
    d = 0.001
    out = np.empty((len(pks), 20), dtype=np.uint8)

    def put(mat, off, values, dtype):
        mat[:, off : off + 8] = (
            np.ascontiguousarray(values, dtype=dtype)
            .view(np.uint8)
            .reshape(len(values), 8)
        )

    for i in range(0, len(pks), chunk):
        sl = slice(i, min(i + chunk, len(pks)))
        x0, y0 = _poly_xy(pks[sl])
        x1, y1 = x0 + d, y0 + d
        m = len(x0)
        mat = np.tile(tmpl, (m, 1))
        # envelope: minx, maxx, miny, maxy (LE doubles)
        for k, v in enumerate((x0, x1, y0, y1)):
            put(mat, env_off + 8 * k, v, "<f8")
        # ring: (x0,y0) (x1,y0) (x1,y1) (x0,y1) (x0,y0) (LE doubles)
        ring = (x0, y0, x1, y0, x1, y1, x0, y1, x0, y0)
        for k, v in enumerate(ring):
            put(mat, coords_off + 8 * k, v, "<f8")
        put(mat, rating_off, rating[sl], ">f8")  # msgpack float64 is BE
        contents = [row.tobytes() for row in mat]
        out[sl] = odb.write_blobs_raw(contents)
    return out


def synth_polygon_repo(path, n, *, edit_frac=0.01, seed=0, ds_path="polys"):
    """BASELINE config #3 scaffolding: a repo with one polygon dataset of
    ``n`` features (real blobs — the value-materialisation path must read,
    inflate and decode them) and two commits: base + an ``edit_frac``
    rating rewrite. -> (repo, info dict)."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.crs import WGS84_WKT
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3

    repo = KartRepo.init_repository(path)
    repo.config.set_many(
        {"user.name": "Synth", "user.email": "synth@example.com"}
    )
    odb = repo.odb

    base = 1 << 24
    pks = np.arange(base, base + n, dtype=np.int64)
    with odb.bulk_pack(level=0):
        oids1 = _write_poly_blobs(odb, pks, pks / 2.0)

    n_edits = max(1, int(n * edit_frac)) if edit_frac else 0
    rng = np.random.default_rng(seed + 1)
    edit_rows = (
        np.sort(rng.choice(n, size=n_edits, replace=False))
        if n_edits
        else np.zeros(0, np.int64)
    )
    oids2 = oids1.copy()
    if n_edits:
        with odb.bulk_pack(level=0):
            oids2[edit_rows] = _write_poly_blobs(
                odb, pks[edit_rows], pks[edit_rows].astype(np.float64)
            )

    plan = plan_int_feature_tree(pks)
    commits = []
    prev = None
    for oids_u8, message in ((oids1, "polygon import"), (oids2, "polygon edits")):
        with odb.bulk_pack(level=0):
            ftree, leaf_oids = emit_feature_tree(odb, plan, oids_u8, prev=prev)
            prev = (leaf_oids, edit_rows)
            tb = TreeBuilder(odb, repo.head_tree_oid if commits else None)
            for blob_path, data in Dataset3.new_dataset_meta_blobs(
                ds_path,
                POLY_SCHEMA,
                title="synthetic polygon layer",
                crs_defs={"EPSG:4326": WGS84_WKT},
                path_encoder=PathEncoder.INT_PK_ENCODER,
            ):
                tb.insert(blob_path, odb.write_blob(data))
            tb.insert(
                f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature", ftree, mode=MODE_TREE
            )
            root = tb.flush()
        commit_oid = repo.create_commit(
            "HEAD", root, message, [commits[-1]] if commits else []
        )
        commits.append(commit_oid)
        sidecar.save_sidecar(repo, ftree, pks, oids_u8)

    return repo, {
        "base_commit": commits[0],
        "edit_commit": commits[1],
        "n": n,
        "n_edits": n_edits,
    }
