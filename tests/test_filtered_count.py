"""`kart diff -o feature-count` under a polygonal spatial filter is exact
(kart_tpu/diff/engine.py get_dataset_feature_count_fast: envelope prefilter,
compaction, classify of the survivors, exact refine of the changed ones):
the count is the number of features `-o json-lines` lists in the same
repository, and the number an independent even-odd ray cast gives
(benchmarks/references/feature_count_filtered.py) — never the count of the
filter's bounding box, which is what the route printed before. Also: the
batched envelope-against-polygon relation against the scalar one, row by
row; the spans and counters of the filtered route."""

import importlib.util
import json
import os
import re

import numpy as np
import pytest
from click.testing import CliRunner

from helpers import edit_commit, make_imported_repo

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


def bench_module(kind, name):
    """benchmarks/<kind>/<name>.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RAY = bench_module("references", "feature_count_filtered")

# fid i of make_imported_repo sits at (100 + i, -40 - 0.1 i). The filter is
# a box 100..108 x -42..-39 with a notch cut out of its top edge, 103.5..105.5
# wide and down to -40.45: fid 4 (104, -40.4) lies in the notch — inside the
# box, outside the polygon — fid 5 (105, -40.5) just below it, inside.
NOTCH = [
    (100, -42), (108, -42), (108, -39), (105.5, -39), (105.5, -40.45),
    (103.5, -40.45), (103.5, -39), (100, -39), (100, -42),
]
RECT = [(100, -42), (105.5, -42), (105.5, -39), (100, -39), (100, -42)]
TRIANGLE = [(100, -42), (108, -42), (100, -39), (100, -42)]


def spec_string(ring):
    return "EPSG:4326;POLYGON((" + ", ".join(f"{x} {y}" for x, y in ring) + "))"


def set_filter(repo, ring):
    from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

    spec = ResolvedSpatialFilterSpec.from_spec_string(spec_string(ring))
    repo.config.set_many(spec.config_items())
    return spec


def write_envelope_sidecar(repo, rev, ds_path):
    """The KCOL sidecar of one revision with its envelope column, made from
    the features themselves (an import writes none)."""
    from kart_tpu.diff import sidecar

    ds = repo.structure(rev).datasets[ds_path]
    _, pks, oids = ds.feature_index()
    envelopes = [
        sidecar._feature_envelope_wsen(ds.get_feature([int(pk)]), "geom") for pk in pks
    ]
    sidecar.save_sidecar(
        repo, ds.feature_tree.oid, np.asarray(pks, dtype=np.int64), oids,
        envelopes=np.asarray(envelopes, dtype=np.float64),
    )


def cli_count(repo_path):
    r = CliRunner().invoke(
        _cli(), ["-C", str(repo_path), "diff", "HEAD^...HEAD", "-o", "feature-count"]
    )
    assert r.exit_code == 0, r.output
    counts = re.findall(r"(\d+) features? changed", r.output)
    return int(counts[0]) if counts else 0


def cli_jsonl_features(repo_path):
    r = CliRunner().invoke(
        _cli(), ["-C", str(repo_path), "diff", "HEAD^...HEAD", "-o", "json-lines"]
    )
    assert r.exit_code == 0, r.output
    return [
        line for line in map(json.loads, r.output.splitlines())
        if line.get("type") == "feature"
    ]


def _cli():
    from kart_tpu.cli import cli

    return cli


def fast_count(repo, ds_path, spec):
    from kart_tpu.diff.engine import get_dataset_feature_count_fast

    return get_dataset_feature_count_fast(
        repo.structure("HEAD^"), repo.structure("HEAD"), ds_path,
        spatial_filter_spec=spec,
    )


def point(x, y):
    from kart_tpu.geometry import Geometry

    return Geometry.from_wkt(f"POINT ({x} {y})")


def feature(repo, ds_path, fid, **changed):
    return {**repo.datasets()[ds_path].get_feature([fid]), **changed}


def edits_attribute_only(repo, ds):
    # fid 2 inside, fid 4 in the notch (the old over-count), fid 5 just
    # below the notch, fid 9 outside the box
    return dict(updates=[feature(repo, ds, f, name="e") for f in (2, 4, 5, 9)])


def edits_null_geometry(repo, ds):
    # a NULL geometry always matches: fid 9 (outside) loses its geometry
    return dict(updates=[feature(repo, ds, 9, geom=None), feature(repo, ds, 10, name="e")])


def edits_move_across_the_edge(repo, ds):
    # fid 2 leaves the polygon for the notch, fid 4 leaves the notch for the
    # polygon, fid 9 moves about outside: an update counts once, and counts
    # when either side matches
    return dict(updates=[
        feature(repo, ds, 2, geom=point(104.5, -39.5)),
        feature(repo, ds, 4, geom=point(101.0, -41.0)),
        feature(repo, ds, 9, geom=point(120.0, -10.0)),
    ])


def edits_insert_and_delete(repo, ds):
    return dict(
        inserts=[
            {"fid": 100, "geom": point(101.0, -41.0), "name": "in", "rating": 1.0},
            {"fid": 101, "geom": point(104.5, -39.5), "name": "notch", "rating": 1.0},
            {"fid": 102, "geom": point(150.0, 10.0), "name": "far", "rating": 1.0},
        ],
        deletes=[3, 4, 9],  # inside, in the notch, outside the box
    )


CASES = {
    # name: (edits, filter ring, the count)
    "notch-attribute-edits": (edits_attribute_only, NOTCH, 2),
    "notch-null-geometry": (edits_null_geometry, NOTCH, 1),
    "notch-move-across-the-edge": (edits_move_across_the_edge, NOTCH, 2),
    "notch-insert-and-delete": (edits_insert_and_delete, NOTCH, 2),
    "rectangle-attribute-edits": (edits_attribute_only, RECT, 3),
    "triangle-attribute-edits": (edits_attribute_only, TRIANGLE, 1),
    "triangle-insert-and-delete": (edits_insert_and_delete, TRIANGLE, 2),
}


@pytest.mark.parametrize("case", CASES)
def test_count_is_exact_and_equals_json_lines(case, tmp_path):
    make_edits, ring, want = CASES[case]
    repo, ds = make_imported_repo(tmp_path, n=12)
    edit_commit(repo, ds, **make_edits(repo, ds))
    for rev in ("HEAD^", "HEAD"):
        write_envelope_sidecar(repo, rev, ds)
    spec = set_filter(repo, ring)
    # through get_dataset_feature_count_fast, not the delta path's fallback
    assert fast_count(repo, ds, spec) == want
    assert cli_count(tmp_path / "repo") == want
    assert len(cli_jsonl_features(tmp_path / "repo")) == want


def test_the_bounding_box_count_is_gone(tmp_path):
    """What the route printed before: every changed survivor of the envelope
    prefilter, so a triangle counted what its bounding rectangle counts."""
    repo, ds = make_imported_repo(tmp_path, n=12)
    edit_commit(repo, ds, **edits_attribute_only(repo, ds))
    for rev in ("HEAD^", "HEAD"):
        write_envelope_sidecar(repo, rev, ds)
    box = [(100, -42), (108, -42), (108, -39), (100, -39), (100, -42)]
    in_box = fast_count(repo, ds, set_filter(repo, box))
    in_triangle = fast_count(repo, ds, set_filter(repo, TRIANGLE))
    assert (in_box, in_triangle) == (3, 1)


# -- a seeded layer of the benchmark's builder against the ray cast ------------

def star(centre, semi, seed, n=24):
    r = np.random.default_rng(seed).uniform(0.6, 1.0, n)
    a = 2 * np.pi * np.arange(n) / n
    ring = np.stack(
        [centre[0] + semi[0] * r * np.cos(a), centre[1] + semi[1] * r * np.sin(a)], 1
    )
    return np.concatenate([ring, ring[:1]]).tolist()


BIG_NOTCH = [
    [-90, -40], [110, -40], [110, 55], [40, 55], [40, -10], [-20, -10], [-20, 55],
    [-90, 55], [-90, -40],
]
LAYER_FILTERS = {
    "star": star((12.0, 8.0), (100.0, 45.0), 7),
    "concave-notch": BIG_NOTCH,
    "rectangle": [[-90, -40], [110, -40], [110, 55], [-90, 55], [-90, -40]],
    "triangle": [[-90, -40], [110, -40], [-90, 55], [-90, -40]],
}


@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    """The benchmark's builder at 6,000 rows, 5% edited, seeded: a
    repository whose blobs are all there, envelope column in the sidecar."""
    builder = bench_module("layers", "nodes_filtered_layer")
    with open(os.path.join(BENCH, "configs", "baseline4_nodes_10m_filtered.json")) as f:
        params = dict(json.load(f)["layer"]["params"], rows=6000, edit_frac=0.05)
    base = tmp_path_factory.mktemp("filtered-base")
    builder.build_base(str(base), params)
    work = tmp_path_factory.mktemp("filtered-work")
    path, info = builder.add_edit_commit(str(base), str(work), params, 2147483653)
    from kart_tpu.core.repo import KartRepo

    return KartRepo(path), info


@pytest.mark.parametrize("name", LAYER_FILTERS)
def test_seeded_layer_count_equals_the_ray_cast_and_json_lines(name, layer):
    repo, info = layer
    ring = np.asarray(LAYER_FILTERS[name], dtype=np.float64)
    spec = set_filter(repo, ring.tolist())
    xy = info["edit_xy"]
    want = int(np.count_nonzero(RAY.points_in_ring(ring, xy[:, 0], xy[:, 1])))
    assert 0 < want < info["n_edits"]
    assert fast_count(repo, "layer", spec) == want
    assert cli_count(repo.workdir) == want
    listed = cli_jsonl_features(repo.workdir)
    inside = info["edit_pks"][RAY.points_in_ring(ring, xy[:, 0], xy[:, 1])]
    assert [f["change"]["+"]["fid"] for f in listed] == inside.tolist()


def test_seeded_layer_triangle_counts_fewer_than_its_box(layer):
    repo, _ = layer
    counts = {
        name: fast_count(repo, "layer", set_filter(repo, LAYER_FILTERS[name]))
        for name in ("rectangle", "triangle")
    }
    assert counts["triangle"] < 0.6 * counts["rectangle"]


# -- the batched relation against the scalar one --------------------------------

def ring_of(centre, semi, seed, n):
    return np.asarray(star(centre, semi, seed, n), dtype=np.float64)


POLYGON_SETS = {
    "one-star": [(ring_of((10, 5), (60, 40), 1, 48), [])],
    "star-with-hole": [(ring_of((10, 5), (60, 40), 1, 48), [ring_of((10, 5), (12, 8), 2, 12)])],
    "two-parts": [
        (ring_of((10, 5), (60, 40), 1, 48), [ring_of((10, 5), (12, 8), 2, 12)]),
        (ring_of((120, -40), (15, 10), 3, 9), []),
    ],
    "rectangle": [(np.array([(0, 0), (40, 0), (40, 30), (0, 30), (0, 0)], float), [])],
}


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("name", POLYGON_SETS)
def test_batched_env_relation_equals_the_scalar_one(name, seed):
    from kart_tpu.spatial_filter import (
        ENV_CONTAINS, ENV_DISJOINT, ENV_PARTIAL, _polygon_set_env_relation,
        polygon_set_env_relations,
    )

    parts = POLYGON_SETS[name]
    rng = np.random.default_rng(seed)
    m = 1500
    x0, y0 = rng.uniform(-80, 150, m), rng.uniform(-70, 60, m)
    x1 = x0 + rng.choice([0.0, 0.001, 0.5, 5.0, 60.0], m)
    y1 = y0 + rng.choice([0.0, 0.001, 0.5, 5.0, 60.0], m)
    # corners and edges that coincide with the polygon's own
    k = min(20, len(parts[0][0]))
    x0[:k], y0[:k] = parts[0][0][:k, 0], parts[0][0][:k, 1]
    x1[:k], y1[:k] = x0[:k] + 1.0, y0[:k] + 1.0
    code = {"disjoint": ENV_DISJOINT, "contains": ENV_CONTAINS, "partial": ENV_PARTIAL}
    want = [
        code[_polygon_set_env_relation(parts, (x0[i], x1[i], y0[i], y1[i]))]
        for i in range(m)
    ]
    got = polygon_set_env_relations(parts, x0, x1, y0, y1)
    assert got.dtype == np.uint8 and got.tolist() == want
    assert len(set(want)) == 3, "the boxes meet every verdict"


def test_batched_env_relation_leaves_what_it_cannot_decide_to_the_geometry():
    from kart_tpu.spatial_filter import ENV_PARTIAL, polygon_set_env_relations

    parts = POLYGON_SETS["rectangle"]
    # wraps the anti-meridian; not a number; the whole world (a NULL
    # geometry's envelope in the sidecar)
    x0 = np.array([170.0, np.nan, -180.0])
    x1 = np.array([-170.0, 1.0, 180.0])
    y0 = np.array([1.0, 1.0, -90.0])
    y1 = np.array([2.0, 2.0, 90.0])
    assert polygon_set_env_relations(parts, x0, x1, y0, y1).tolist() == [ENV_PARTIAL] * 3
    assert polygon_set_env_relations(parts, x0[:0], x1[:0], y0[:0], y1[:0]).tolist() == []


# -- spans and counters -----------------------------------------------------------

def test_filtered_count_names_its_stages(layer, tmp_path):
    from kart_tpu import telemetry as tm

    repo, info = layer
    set_filter(repo, LAYER_FILTERS["star"])
    trace = tmp_path / "spans.json"
    tm.reset()
    tm.enable(metrics=True, trace=True, trace_path=str(trace))
    try:
        count = cli_count(repo.workdir)
        counters = {
            name: v for (name, _), v in tm.counters_snapshot().items()
        }
    finally:
        tm.reset()
    with open(trace) as f:
        events = {
            e["name"]: e["args"] for e in json.load(f)["traceEvents"] if e.get("ph") == "X"
        }
    for child in ("scan", "propagate", "compact"):
        assert events[f"diff.prefilter.{child}"]["parent"] == "diff.prefilter"
    for name in ("diff.prefilter", "diff.classify", "diff.refine"):
        assert events[name]["parent"] == "cli.command"
    scan, compact = events["diff.prefilter.scan"], events["diff.prefilter.compact"]
    refine = events["diff.refine"]
    assert scan["rows"] == compact["rows"] == 12000
    assert scan["hits_old"] == scan["hits_new"] > 0
    assert 0 < scan["blocks_scanned"] <= scan["blocks"]
    assert events["diff.prefilter.propagate"]["probed"] == 0
    assert compact["survivors"] == scan["hits_old"] + scan["hits_new"]
    assert compact["bytes"] == compact["survivors"] * 28 and compact["runs"] >= 2
    assert events["diff.classify"]["rows"] == scan["hits_old"]
    assert events["diff.classify"]["counts_only"] is False
    assert refine["candidates"] == refine["inside"] + refine["outside"] + refine["residue"]
    assert refine["blobs_read"] == refine["residue"]
    # an attribute edit changes both sides of a row alike: two candidates a delta
    assert refine["inside"] == 2 * count and refine["candidates"] > refine["inside"]
    assert counters["diff.prefilter.rows_kept"] == compact["survivors"]
    assert counters["diff.refine.residue_rows"] == refine["residue"]


def test_unfiltered_count_enters_none_of_it(layer, tmp_path):
    from kart_tpu import telemetry as tm

    repo, info = layer
    for key in set_filter(repo, LAYER_FILTERS["star"]).config_items():
        repo.del_config(key)
    trace = tmp_path / "spans.json"
    tm.reset()
    tm.enable(metrics=True, trace=True, trace_path=str(trace))
    try:
        assert cli_count(repo.workdir) == info["n_edits"]
    finally:
        tm.reset()
    with open(trace) as f:
        events = {e["name"]: e.get("args", {}) for e in json.load(f)["traceEvents"]}
    assert not [n for n in events if n.startswith(("diff.prefilter", "diff.refine"))]
    assert events["diff.classify"]["counts_only"] is True
    assert "diff.changed_indices" not in events


def test_the_compaction_gathers_into_memory_it_keeps(layer):
    """Two filtered commands in one process: the second call's oid columns
    lie where the first call's did (no fresh 59 MB a side a command at the
    benchmark's size), hold the second call's rows, and a smaller survivor
    set takes the front of the same buffer."""
    from kart_tpu.diff import sidecar
    from kart_tpu.diff.engine import _prefilter_rect, spatial_prefilter_blocks

    repo, _ = layer
    blocks = [
        sidecar.load_block(repo, repo.structure(rev).datasets["layer"], pad=False)
        for rev in ("HEAD^", "HEAD")
    ]

    def survivors(name):
        rect = _prefilter_rect(set_filter(repo, LAYER_FILTERS[name]))
        (old_sub, new_sub), (old_rows, new_rows) = spatial_prefilter_blocks(*blocks, rect)
        for sub, rows, block in ((old_sub, old_rows, blocks[0]), (new_sub, new_rows, blocks[1])):
            np.testing.assert_array_equal(sub.keys, np.asarray(block.keys)[rows])
            np.testing.assert_array_equal(sub.oids, np.asarray(block.oids)[rows])
        return old_sub, new_sub

    first = survivors("rectangle")
    at = [sub.oids.ctypes.data for sub in first]
    second = survivors("rectangle")
    assert [sub.oids.ctypes.data for sub in second] == at
    smaller = survivors("star")
    assert [sub.oids.ctypes.data for sub in smaller] == at
    assert 0 < smaller[0].count < second[0].count
    assert not np.shares_memory(smaller[0].oids, smaller[1].oids)
