"""The filtered `-o feature-count`'s changed-rows route
(kart_tpu/diff/engine.py get_dataset_feature_count_fast): where the backend
keeps the revisions' pages on the device and the sidecar's block census
bounds the rectangle's keep share at or above ``CHANGED_ROUTE_MIN_SHARE``,
the whole pair is classified on those pages and the prefilter's survivors
rule runs behind the classify, on the changed rows alone
(``changed_rows_in_rect``). Held here, with the one-device route forced
onto XLA-CPU, to the rows route (the same rule before the classify) and to
the host engine: the same count, the same refine, the same output bytes;
the route chosen by what can be observed; a second command a page-store
hit."""

import json
import pathlib

import numpy as np
import pytest
from click.testing import CliRunner

from helpers import edit_commit, make_imported_repo
from test_filtered_count import (
    NOTCH,
    cli_jsonl_features,
    feature,
    point,
    set_filter,
    write_envelope_sidecar,
)

from kart_tpu import telemetry as tm
from kart_tpu.diff import engine, sidecar
from kart_tpu.ops import resident

# fid i of make_imported_repo sits at (100 + i, -40 - 0.1 i). NOTCH's
# bounding rectangle is 100..108 x -42..-39, padded by the prefilter's 1e-4
# degrees: 108.00005 lies inside the padded edge (and outside the polygon),
# 108.0003 outside it.
JUST_IN, JUST_OUT = 108.00005, 108.0003


def edits_move_across_the_edge(repo, ds):
    # fid 2 leaves the rectangle, fid 10 enters it, fid 12 moves about
    # outside: an update counts when either side matches
    return dict(updates=[
        feature(repo, ds, 2, geom=point(120.0, -10.0)),
        feature(repo, ds, 10, geom=point(101.5, -41.0)),
        feature(repo, ds, 12, geom=point(113.0, -41.0)),
    ])


def edits_update_one_side_in(repo, ds):
    # fid 3 leaves the rectangle just past its padded edge, fid 11 enters
    # the pad alone (inside the rectangle, outside the polygon), fid 6 is
    # an attribute edit inside
    return dict(updates=[
        feature(repo, ds, 3, geom=point(JUST_OUT - 0.0001, -40.3)),
        feature(repo, ds, 11, geom=point(JUST_IN, -41.1)),
        feature(repo, ds, 6, name="e"),
    ])


def edits_at_the_padded_edge(repo, ds):
    def insert(fid, x, y):
        return {"fid": fid, "geom": point(x, y), "name": "i", "rating": 1.0}

    return dict(
        inserts=[
            insert(100, JUST_IN, -40.0),  # in the pad, east
            insert(101, JUST_OUT, -40.0),  # past it
            insert(102, 99.99995, -41.0),  # in the pad, west
            insert(103, 101.0, -38.99995),  # in the pad, north
            insert(104, 101.0, -38.9997),  # past it
            insert(105, 101.5, -41.5),  # inside the polygon
        ],
        deletes=[1, 9, 12],  # inside, just outside, far outside
    )


def edits_all_outside(repo, ds):
    # the changed set is not empty, the survivors are
    return dict(
        updates=[feature(repo, ds, 9, name="e"), feature(repo, ds, 10, name="e")],
        inserts=[{"fid": 100, "geom": point(150.0, 10.0), "name": "i", "rating": 1.0}],
        deletes=[12],
    )


def edits_attribute_only(repo, ds):
    # fid 2 inside, fid 4 in the notch (inside the rectangle only), fid 9
    # outside
    return dict(updates=[feature(repo, ds, f, name="e") for f in (2, 4, 9)])


CASES = {
    # name: (edits, the count)
    "move-across-the-edge": (edits_move_across_the_edge, 2),
    "update-one-side-in": (edits_update_one_side_in, 2),
    "inserts-and-deletes-at-the-padded-edge": (edits_at_the_padded_edge, 2),
    "all-outside": (edits_all_outside, 0),
    "attribute-edits-in-the-notch": (edits_attribute_only, 1),
}


def make_layer(tmp_path, make_edits, monkeypatch, agg_rows=4):
    """A 12-row repository, one edit commit, envelope sidecars with an
    aggregate block every ``agg_rows`` rows (so that the census has blocks
    to tell apart), NOTCH set as its filter."""
    monkeypatch.setattr(sidecar, "AGG_BLOCK_ROWS", agg_rows)
    repo, ds = make_imported_repo(tmp_path, n=12)
    edit_commit(repo, ds, **make_edits(repo, ds))
    for rev in ("HEAD^", "HEAD"):
        write_envelope_sidecar(repo, rev, ds)
    set_filter(repo, NOTCH)
    return repo


@pytest.fixture
def device(monkeypatch):
    """The one-device route forced and a page store of this test's own."""
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.delenv("KART_DIFF_BACKEND", raising=False)
    pages = resident.PageStore(budget_bytes=1 << 30)
    monkeypatch.setattr(resident, "PAGES", pages)
    yield pages


#: how each route is reached: (environment, CHANGED_ROUTE_MIN_SHARE)
ROUTES = {
    "changed": ({"KART_DIFF_DEVICE": "1"}, None),
    # no census reaches 2: the rows route on the same device backend
    "rows": ({"KART_DIFF_DEVICE": "1"}, 2.0),
    "host": ({"KART_DIFF_BACKEND": "host_native"}, None),
    "mesh": ({"KART_DIFF_BACKEND": "sharded_jax"}, None),
}


def run_count(repo, monkeypatch, route):
    """One `kart diff HEAD^...HEAD -o feature-count` by ``route`` -> (its
    output bytes, span args by name (the last of a name), the names of
    every span in order, ``diff.prefilter.route`` counts by ``where``)."""
    from kart_tpu.cli import cli

    env, min_share = ROUTES[route]
    here = pathlib.Path(repo.workdir).parent
    trace = here / f"spans-{len(list(here.glob('spans-*')))}.json"
    with monkeypatch.context() as m:
        for knob in ("KART_DIFF_DEVICE", "KART_DIFF_BACKEND"):
            m.delenv(knob, raising=False)
        for knob, value in env.items():
            m.setenv(knob, value)
        if min_share is not None:
            m.setattr(engine, "CHANGED_ROUTE_MIN_SHARE", min_share)
        tm.reset()
        tm.enable(metrics=True, trace=True, trace_path=str(trace))
        try:
            r = CliRunner().invoke(
                cli, ["-C", str(repo.workdir), "diff", "HEAD^...HEAD", "-o", "feature-count"]
            )
            counters = tm.counters_snapshot()
        finally:
            tm.reset()
    assert r.exit_code == 0, r.output
    routes = {
        dict(labels)["where"]: v for (name, labels), v in counters.items()
        if name == "diff.prefilter.route"
    }
    assert not any(name == "diff.device.fallbacks" for name, _ in counters)
    with open(trace) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    return (
        r.stdout_bytes,
        {e["name"]: e.get("args", {}) for e in spans},
        [e["name"] for e in spans],
        routes,
    )


def blocks(repo):
    """Both revisions' sidecar blocks, as the count loads them."""
    return [
        sidecar.load_block(repo, repo.structure(rev).datasets["points"], pad=False)
        for rev in ("HEAD^", "HEAD")
    ]


def refine_census(args):
    refine = args["diff.refine"]
    return {k: refine[k] for k in ("candidates", "inside", "outside", "residue")}


@pytest.mark.parametrize("case", CASES)
def test_every_route_gives_one_answer(case, tmp_path, monkeypatch, device):
    make_edits, want = CASES[case]
    repo = make_layer(tmp_path, make_edits, monkeypatch)
    answers = {route: run_count(repo, monkeypatch, route) for route in ("changed", "rows", "host")}
    out, args, names, routes = answers["changed"]
    assert routes == {"changed": 1}
    assert "diff.prefilter.scan" not in names
    assert args["diff.classify"]["backend"] == "device_jax"
    assert args["diff.classify"]["rows"] == max(b.count for b in blocks(repo))
    for route in ("rows", "host"):
        other_out, other_args, _, other_routes = answers[route]
        assert other_routes == {"rows": 1}
        assert other_out == out, route
        assert refine_census(other_args) == refine_census(args), route
    assert out.decode().count(f"{want} features changed") == (1 if want else 0)
    assert len(cli_jsonl_features(repo.workdir)) == want


#: name -> (route's environment, CHANGED_ROUTE_MIN_SHARE from the census
#: bound (None: as it is), the route taken, whether the census runs)
CHOICES = {
    "pages-kept-bound-at-the-constant": ("changed", lambda bound: bound, "changed", True),
    "pages-kept-bound-below-the-constant": (
        "changed", lambda bound: float(np.nextafter(bound, 2.0)), "rows", True,
    ),
    "host-engine": ("host", None, "rows", False),
    "mesh": ("mesh", None, "rows", False),
}


@pytest.mark.parametrize("choice", CHOICES)
def test_the_route_is_chosen_from_what_can_be_observed(choice, tmp_path, monkeypatch, device):
    route, min_share, want, census_runs = CHOICES[choice]
    repo = make_layer(tmp_path, edits_attribute_only, monkeypatch)
    query = np.asarray(engine._prefilter_rect(set_filter(repo, NOTCH)), dtype=np.float64)
    bound = max(engine.block_census(b, query)[0] for b in blocks(repo))
    # fids 9..12 (one aggregate block a side of three) lie east of the box
    assert bound == pytest.approx(8 / 12)
    if min_share is not None:
        monkeypatch.setattr(engine, "CHANGED_ROUTE_MIN_SHARE", min_share(bound))
    out, args, names, routes = run_count(repo, monkeypatch, route)
    assert routes == {want: 1}
    assert out.decode().count("1 features changed") == 1
    assert ("diff.prefilter.census" in names) == census_runs
    if census_runs:
        census = args["diff.prefilter.census"]
        assert census["parent"] == "diff.prefilter"
        assert (census["blocks"], census["bound_share"]) == (6, bound)
    if want == "changed":
        assert args["diff.prefilter.changed"]["parent"] == "diff.prefilter"
        # three updates, both sides; fid 9's keys are east of the box
        changed = args["diff.prefilter.changed"]
        assert (changed["rows"], changed["survivors"]) == (6, 4)
        # fid 2's and fid 4's block lies inside the box, fid 9's east of
        # it: the census decides all six rows, none reads its envelope
        assert changed["envelopes_read"] == 0
        assert not [n for n in names if n in PREFILTER_ROWS_STAGES]
    else:
        # the rows route's stages, as they always were
        for stage in PREFILTER_ROWS_STAGES:
            assert args[stage]["parent"] == "diff.prefilter"
        assert "diff.prefilter.changed" not in names
    assert args["diff.refine"]["candidates"] == 4


PREFILTER_ROWS_STAGES = (
    "diff.prefilter.scan", "diff.prefilter.propagate", "diff.prefilter.compact",
)


def test_a_second_filtered_count_finds_every_page(tmp_path, monkeypatch, device):
    """Both whole revisions' pages stay on the device: the second command
    of a process ships nothing."""
    repo = make_layer(tmp_path, edits_move_across_the_edge, monkeypatch)
    first = run_count(repo, monkeypatch, "changed")
    second = run_count(repo, monkeypatch, "changed")
    before, after = first[1]["diff.classify"], second[1]["diff.classify"]
    assert before["resident_bytes"] == 0 < before["input_bytes"]
    assert after["resident_bytes"] == after["input_bytes"] == before["input_bytes"]
    assert second[0] == first[0] and second[3] == {"changed": 1}
    trees = {b.tree_oid for b in blocks(repo)}
    assert {key[0] for key in device.keys()} == trees


def test_an_empty_changed_set_keeps_nothing_and_counts_nothing(tmp_path, monkeypatch):
    from kart_tpu.ops.diff_kernel import UNCHANGED

    repo = make_layer(tmp_path, edits_attribute_only, monkeypatch)
    old, new = blocks(repo)
    query = np.asarray(engine._prefilter_rect(set_filter(repo, NOTCH)), dtype=np.float64)
    classes = tuple(np.full(b.count, UNCHANGED, dtype=np.int8) for b in (old, new))
    none = np.zeros(0, dtype=np.int64)
    survivors = engine.changed_rows_in_rect(
        old, new, classes, (none, none), query, [engine.block_census(b, query)[1] for b in (old, new)]
    )
    assert [(len(rows), len(upd)) for rows, upd in survivors] == [(0, 0), (0, 0)]
    spec = set_filter(repo, NOTCH)
    datasets = [repo.structure(rev).datasets["points"] for rev in ("HEAD^", "HEAD")]
    sides = tuple((ds, b, rows, upd) for ds, b, (rows, upd) in zip(datasets, (old, new), survivors))
    assert engine.refine_changed_count(spec, sides) == 0


@pytest.mark.parametrize("block_rows", [1, 7, 64, 1000])
@pytest.mark.parametrize("seed", [3, 4])
def test_the_census_bounds_the_keep_share_and_decides_rows_as_the_scan(block_rows, seed):
    """The bound is the rows of the aggregate blocks not called all-out
    over the rows — never below the share the scan keeps — and a changed
    row's answer from its block's class, or its own envelope in a boundary
    block, is the scan's answer for it."""
    from kart_tpu.native import bbox_intersects_f32
    from kart_tpu.ops.bbox import BLOCK_ALL_OUT, BLOCK_BOUNDARY, classify_env_blocks_np
    from kart_tpu.ops.blocks import FeatureBlock

    rng = np.random.default_rng(seed)
    n = 5000
    # rows in bands of latitude, so that neighbouring rows lie near one
    # another as in a layer
    xy = rng.uniform([-180, -80], [180, 80], (n, 2))
    xy = xy[np.lexsort((xy[:, 0], np.floor(xy[:, 1] / 10)))]
    env = np.ascontiguousarray(xy[:, [0, 1, 0, 1]], dtype=np.float32)  # w s e n
    env[2000:2003] = np.nan  # empty geometries: a block whose aggregate is flagged
    agg, flags = sidecar._block_aggregates(env, block_rows)
    block = FeatureBlock(
        np.arange(n, dtype=np.int64), np.zeros((n, 5), dtype=np.uint32), None, n,
        envelopes=env, env_blocks=(agg, flags, block_rows),
    )
    query = np.array([-20.0, -15.0, 40.0, 30.0])
    bound, cls = engine.block_census(block, query)
    np.testing.assert_array_equal(cls, classify_env_blocks_np(agg, flags, query))
    met = np.repeat(cls != BLOCK_ALL_OUT, block_rows)[:n]
    assert len(cls) == -(-n // block_rows)
    assert bound == np.count_nonzero(met) / n
    hits = bbox_intersects_f32(env, query)
    assert 0 < np.count_nonzero(hits) / n <= bound < 1
    assert not np.any(hits & ~met)

    rows = np.sort(rng.choice(n, 700, replace=False))
    got, read = engine._rows_meet(block, rows, query, cls)
    np.testing.assert_array_equal(got, hits[rows])
    assert read == np.count_nonzero(cls[rows // block_rows] == BLOCK_BOUNDARY)
    bare = FeatureBlock(block.keys, block.oids, None, n, envelopes=env)
    assert engine.block_census(bare, query) == (1.0, None)
    got, read = engine._rows_meet(bare, rows, query, None)
    np.testing.assert_array_equal(got, hits[rows])
    assert read == len(rows)
    empty = FeatureBlock(block.keys[:0], block.oids[:0], None, 0, envelopes=env[:0])
    assert engine.block_census(empty, query) == (0.0, None)
