"""The filtered-clone deployment (``baseline4_nodes_10m_filtered``) at a small
size on the CPU: the configuration's filter is what its file says it is and
keeps what it says it keeps; the builder's edit sets (seeded, counted, clear
of the filter's edges); the reference's ray cast on shapes whose answer is
known by hand, and against the program through the CLI; the ten new metrics
on hand-made span events, on a traced rehearsal and on the parent's trace."""

import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)

from test_benchmark_mesh_readers import read_metric, reference  # noqa: E402
from test_benchmark_span_readers import module, span  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL = "nodes10m.diff_count.filtered"
CONFIG = "baseline4_nodes_10m_filtered"
SEED = 2147483653  # past 32 signed bits, as the driver's are
WINDOW = "jit__classify_mergesort_core_window_split(11)"
SPAN_METRICS = {
    "prefilter.span_s": "diff.prefilter",
    "prefilter.census_s": "diff.prefilter.census",
    "prefilter.changed_s": "diff.prefilter.changed",
    "refine.span_s": "diff.refine",
}
#: the cell's metrics: its spans', the changed-rows route's three shares, the
#: refine's residue and the kernel's two (the rows route's scan, propagate
#: and compact have no cell since the census sends the cell's count to the
#: changed-rows route)
NEW_METRICS = [
    *SPAN_METRICS, "prefilter.bound_share", "prefilter.changed_keep_share",
    "prefilter.envelope_read_share", "refine.residue_share",
    "kernel.filtered_classify_s", "kernel.filtered_classify_roofline",
]


def builder():
    """benchmarks/layers/nodes_filtered_layer.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_layers_nodes_filtered_layer",
        os.path.join(BENCH, "layers", "nodes_filtered_layer.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def config_params(rows, **changed):
    return dict(config()["layer"]["params"], rows=rows, **changed)


# -- the configuration -------------------------------------------------------------

def test_the_filter_is_the_polygon_its_constants_give():
    shape = config()["layer"]["params"]["filter"]
    ring = np.asarray(shape["ring"])
    n = shape["vertices"]
    assert n == 48 and ring.shape == (n + 1, 2) and (ring[0] == ring[-1]).all()
    radii = np.random.default_rng(shape["radii_seed"]).uniform(shape["radius_min"], 1.0, n)
    angle = 2 * np.pi * np.arange(n) / n
    want = np.asarray(shape["centre"]) + np.asarray(shape["semi_axes"]) * (
        radii[:, None] * np.stack([np.cos(angle), np.sin(angle)], axis=1)
    )
    np.testing.assert_array_equal(ring[:-1], np.round(want, 4))
    assert -180 < ring[:, 0].min() and ring[:, 0].max() < 180  # no anti-meridian
    assert shape["crs"] == "EPSG:4326"


def test_the_filters_box_keeps_three_tenths_and_the_polygon_two_thirds_of_those():
    """The shares the configuration states, at a size numpy takes in a
    moment: the layout is the same function of pk / rows at every size."""
    layer = builder()
    params = config_params(1_000_000)
    ring = layer.filter_ring(params)
    pks = layer.PK_BASE + np.arange(params["rows"])
    x, y = layer.base_layer.origins("POINT", pks, params["rows"])
    in_box = (
        (x >= ring[:, 0].min()) & (x <= ring[:, 0].max())
        & (y >= ring[:, 1].min()) & (y <= ring[:, 1].max())
    )
    assert 0.29 <= in_box.mean() <= 0.30
    ray = reference("feature_count_filtered")
    in_polygon = ray.points_in_ring(ring, x[in_box], y[in_box])
    assert 0.60 <= in_polygon.mean() <= 0.70
    # the survivors of the cell's 10M rows land in the 3,145,728-row bucket
    from kart_tpu.ops.blocks import bucket_size

    assert bucket_size(int(in_box.mean() * 10_000_000)) == 3_145_728


def test_the_configuration_states_the_exact_count_and_what_it_cut():
    cfg = config()
    assert cfg["architecture"] is None
    assert any("never the bounding-box upper bound" in g for g in cfg["guarantees"])
    assert len(cfg["guarantees"]) == 6
    assert cfg["reduced"] == ["rows", "sidecar_vertex_column"]
    assert cfg["layer"]["params"]["rows"] == 10_000_000
    assert cfg["expect_backend"] == {"1": "device_jax"}
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "diff_count_filtered", 1
    )
    check_manifest(MANIFEST)


def check_manifest(manifest):
    """The cell's metrics list it, by name and by membership: a later PR may
    list more for it, and list another cell beside it."""
    reads = [
        m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())
    ]
    assert set(NEW_METRICS) <= set(reads)


# -- the builder's edit sets -------------------------------------------------------

@pytest.mark.parametrize("rows", [3000, 40_000])
def test_edit_rows_are_seeded_counted_and_sorted(rows):
    layer = builder()
    params = config_params(rows)
    strata = layer.row_strata(params)
    picked = layer.edit_rows(params, SEED, strata)
    assert len(picked) == max(1, int(rows * params["edit_frac"]))
    assert (np.diff(picked) > 0).all() and 0 <= picked[0] and picked[-1] < rows
    np.testing.assert_array_equal(picked, layer.edit_rows(params, SEED))
    assert not np.array_equal(picked, layer.edit_rows(params, SEED + 1, strata))
    # away from the configuration's size, each stratum gives its expected count
    counts = layer.expected_counts(strata, len(picked))
    assert layer.edit_counts(params, strata) == counts
    drawn = {name: 0 for name in counts}
    for label in strata[picked]:
        drawn[layer.stratum_name(label)] += 1
    assert drawn == counts


def test_an_edited_point_near_a_filter_edge_is_drawn_again(monkeypatch):
    """With a clearance as wide as the layer's spacing many rows lie near an
    edge: none of them is edited, and the count stays."""
    layer = builder()
    params = config_params(40_000, edit_frac=0.05)
    monkeypatch.setattr(layer, "EDGE_CLEARANCE", 0.5)
    strata = layer.row_strata(params)
    ring = layer.filter_ring(params)
    x, y = layer.base_layer.origins(
        "POINT", layer.PK_BASE + np.arange(params["rows"]), params["rows"]
    )
    near = layer.edge_distance(ring, x, y) < 0.5
    assert near.any()
    # the rows near an edge, and only they, are never edited
    np.testing.assert_array_equal(strata == layer.NEVER, near)
    picked = layer.edit_rows(params, SEED, strata)
    assert len(picked) == 2000 == len(set(picked.tolist()))
    assert (layer.edge_distance(ring, x[picked], y[picked]) >= 0.5).all()


def test_edge_distance_is_the_distance_to_the_nearest_segment():
    layer = builder()
    square = np.array([(0, 0), (10, 0), (10, 10), (0, 10), (0, 0)], dtype=float)
    x = np.array([5.0, 5.0, -3.0, 13.0, 10.0])
    y = np.array([5.0, 1.0, 5.0, 14.0, 2.0])
    np.testing.assert_allclose(
        layer.edge_distance(square, x, y), [5.0, 1.0, 3.0, 5.0, 0.0]
    )


def test_a_rectangle_is_on_the_edge_where_a_segment_of_the_ring_meets_it():
    layer = builder()
    square = np.array([(0, 0), (10, 0), (10, 10), (0, 10), (0, 0)], dtype=float)
    # inside, outside, across an edge, a corner inside, touching an edge
    x0 = np.array([4.0, 12.0, 9.0, -1.0, 10.0])
    y0 = np.array([4.0, 4.0, 4.0, -1.0, 4.0])
    hit = layer.crosses_edge(square, x0, x0 + 1, y0, y0 + 1)
    assert hit.tolist() == [False, False, True, True, True]


@pytest.fixture(scope="module")
def strata_40k():
    """Every row's stratum of a 40,000-row layer, 5% of it edited."""
    params = config_params(40_000, edit_frac=0.05)
    return params, builder().row_strata(params)


def test_the_configurations_counts_are_a_uniform_draws_expected_counts(strata_40k):
    """The counts the configuration states add up to its edits, two on the
    edge; where the layer is the stated size the builder draws them, and a
    layer whose expected counts differ is refused (at the configuration's
    10M rows every run of the cell checks them)."""
    counts = config()["layer"]["params"]["edit_strata"]["counts"]
    assert sum(counts.values()) == 100_000
    assert sum(c for name, c in counts.items() if "on_edge" in name) == 2
    params, strata = strata_40k
    layer = builder()
    want = layer.expected_counts(strata, 2000)
    stated = dict(params["edit_strata"], rows=40_000, counts=want)
    assert layer.edit_counts(dict(params, edit_strata=stated), strata) == want
    (most, _), (other, _) = sorted(want.items(), key=lambda kv: -kv[1])[:2]
    wrong = dict(want, **{most: want[most] - 1, other: want[other] + 1})
    with pytest.raises(ValueError):
        layer.edit_counts(dict(params, edit_strata=dict(stated, counts=wrong)), strata)


@pytest.mark.parametrize("seed", [SEED, 2 ** 31 + 7, 12345])
def test_every_seed_draws_the_configurations_counts(strata_40k, seed):
    """The same work for every seed: as many edits in the box, in the
    polygon, in boundary census blocks and on the edge."""
    params, strata = strata_40k
    layer = builder()
    picked = layer.edit_rows(params, seed, strata)
    assert len(picked) == len(np.unique(picked)) == 2000
    marks = strata[picked]
    got = {name: int(np.count_nonzero(marks & bit)) for bit, name in layer.MARKS.items()}
    counts = layer.expected_counts(strata, 2000)
    want = {
        name: sum(c for stratum, c in counts.items() if name in stratum.split(","))
        for name in layer.MARKS.values()
    }
    assert got == want and want["in_box"] > 0 and want["in_polygon"] > 0


# -- the reference -----------------------------------------------------------------

def test_the_ray_cast_on_shapes_known_by_hand():
    ray = reference("feature_count_filtered")
    # a square with a notch cut out of its top edge
    ring = np.array(
        [(0, 0), (10, 0), (10, 10), (6, 10), (6, 4), (4, 4), (4, 10), (0, 10), (0, 0)],
        dtype=float,
    )
    x = np.array([1.0, 5.0, 5.0, 9.0, 11.0, -1.0, 5.0, 2.0])
    y = np.array([1.0, 2.0, 6.0, 9.0, 5.0, 5.0, 11.0, 9.5])
    want = [True, True, False, True, False, False, False, True]
    assert ray.points_in_ring(ring, x, y).tolist() == want
    assert ray.points_in_ring(ring, x[:0], y[:0]).tolist() == []


def test_the_reference_counts_the_edits_inside_the_configurations_polygon():
    ray = reference("feature_count_filtered")
    ring = ray.filter_ring()
    np.testing.assert_array_equal(ring, builder().filter_ring(config_params(10)))
    centre = config()["layer"]["params"]["filter"]["centre"]
    info = {"edit_xy": np.array([centre, [179.0, 80.0], [centre[0] + 1, centre[1]]])}
    assert ray.edits_in_polygon(info) == 2
    good = b"layer:\n\t2 features changed\n"
    assert ray.check(good, info) == {
        "one_dataset_counted": True, "count_equals_edits_in_polygon": True,
    }
    assert not ray.check(b"layer:\n\t3 features changed\n", info)[
        "count_equals_edits_in_polygon"
    ]
    assert not ray.check(b"", info)["one_dataset_counted"]
    assert "kart_tpu" not in open(ray.__file__).read().replace("the program", "")


@pytest.fixture(scope="module")
def filtered_repo(tmp_path_factory):
    """The builder's repository at 20,000 rows, 5% edited."""
    layer = builder()
    params = config_params(20_000, edit_frac=0.05)
    base = tmp_path_factory.mktemp("nodes-base")
    layer.build_base(str(base), params)
    work = tmp_path_factory.mktemp("nodes-work")
    path, info = layer.add_edit_commit(str(base), str(work), params, SEED)
    return path, info, params


def kart(*argv):
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    result = CliRunner().invoke(cli, list(argv), catch_exceptions=False)
    assert result.exit_code == 0, result.output
    return result.stdout_bytes


def test_the_program_passes_the_reference_and_the_box_count_fails_it(filtered_repo):
    path, info, params = filtered_repo
    with open(os.path.join(BENCH, "traffic", "diff_count_filtered.json")) as f:
        traffic = json.load(f)
    argv = [a.format(repo=path, out="unused") for a in traffic["argv"]]
    output = kart(*argv)
    ray = reference(traffic["reference"])
    checks = ray.check(output, info)
    assert all(checks.values()), checks
    assert set(traffic["rehearsal_checks"]) == set(checks)
    (count,) = (int(c) for c in re.findall(rb"(\d+) features? changed", output))
    assert count == ray.edits_in_polygon(info) < info["n_edits_in_box"] < info["n_edits"]
    # what the route printed before this deployment: the box's count
    box_count = b"layer:\n\t%d features changed\n" % info["n_edits_in_box"]
    assert not ray.check(box_count, info)["count_equals_edits_in_polygon"]
    # the filter is in the repository's configuration, as checkout writes it
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

    spec = ResolvedSpatialFilterSpec.from_repo_config(KartRepo(path))
    assert not spec.match_all and spec.crs_spec == "EPSG:4326"
    w, s, e, n = spec.envelope_wsen_4326
    ring = builder().filter_ring(params)
    assert (w, s, e, n) == (
        ring[:, 0].min(), ring[:, 1].min(), ring[:, 0].max(), ring[:, 1].max()
    )
    # every blob is in the pack: json-lines names the same features
    lines = kart("-C", path, "diff", "HEAD^...HEAD", "-o", "json-lines").splitlines()
    assert sum(b'"type":"feature"' in ln.replace(b" ", b"") for ln in lines) == count


# -- the metrics -------------------------------------------------------------------

def command(t0, survivors=58_900, read=11_128, bound=0.3379, residue=3):
    """The span events of one traced filtered count on the changed-rows
    route that starts at ``t0``: the census, the classify of the whole pair,
    the rectangle test on its 200,000 changed rows, the refine of the
    survivors."""
    return [
        span("cli.command", t0, 0.08),
        span("diff.prefilter", t0 + 0.010, 0.0006, "cli.command", rows=10_000_000),
        span("diff.prefilter.census", t0 + 0.010, 0.0005, "diff.prefilter",
             blocks=4884, bound_share=bound),
        span("diff.classify", t0 + 0.011, 0.028, "cli.command", rows=10_000_000,
             backend="device_jax", counts_only=False),
        span("diff.prefilter", t0 + 0.040, 0.0045, "cli.command", rows=10_000_000),
        span("diff.prefilter.changed", t0 + 0.040, 0.004, "diff.prefilter",
             rows=200_000, envelopes_read=read, survivors=survivors),
        span("diff.refine", t0 + 0.045, 0.016, "cli.command", candidates=survivors,
             inside=37_000, outside=survivors - 37_000 - residue, residue=residue,
             blobs_read=residue),
    ]


def traced_run():
    return {
        "ops_events": [
            command(10.0),
            command(11.0, survivors=58_904, read=11_132, bound=0.3381, residue=5),
        ],
        "xla": [module(100.0 + i, 0.0155, name=WINDOW) for i in range(2)],
        "ops_walls": [0.08, 0.08], "device_kind": "TPU v5 lite",
    }


def test_each_new_metric_reads_its_span_or_program():
    ctx = traced_run()
    durations = {"diff.prefilter": 0.0051, "diff.prefilter.census": 0.0005,
                 "diff.prefilter.changed": 0.004, "diff.refine": 0.016}
    for name, source in SPAN_METRICS.items():
        assert read_metric(name, ctx) == pytest.approx(durations[source]), name
    assert read_metric("prefilter.bound_share", ctx) == pytest.approx(
        100.0 * (0.3379 + 0.3381) / 2
    )
    assert read_metric("prefilter.changed_keep_share", ctx) == pytest.approx(
        100.0 * (58_900 + 58_904) / 400_000
    )
    assert read_metric("prefilter.envelope_read_share", ctx) == pytest.approx(
        100.0 * (11_128 + 11_132) / 400_000
    )
    assert read_metric("refine.residue_share", ctx) == pytest.approx(
        100.0 * 8 / (58_900 + 58_904)
    )
    assert read_metric("kernel.filtered_classify_s", ctx) == pytest.approx(0.0155)
    # 29 B a row over both sides of the whole pair at 819 GB/s, over 0.0155 s
    least = 10_000_000 * 2 * 29 / 819e9
    assert read_metric("kernel.filtered_classify_roofline", ctx) == pytest.approx(
        100.0 * least / 0.0155
    )
    assert read_metric("kernel.filtered_classify_roofline", ctx) < 100.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_is_silent_on_the_parents_trace(name):
    """The parent has neither the child spans nor diff.refine, and an
    unfiltered command has no prefilter at all: nothing to read, no raise."""
    parent = {
        "ops_events": [[
            span("cli.command", 10.0, 0.5),
            span("diff.prefilter", 10.05, 0.4, "cli.command", rows=10_000_000),
        ]],
        "xla": [], "ops_walls": [0.5], "device_kind": "TPU v5 lite",
    }
    value = read_metric(name, parent)
    assert (value is None) == (name != "prefilter.span_s")
    assert read_metric(name, {**parent, "ops_events": [[]]}) is None


def test_each_new_metric_file_names_a_reader_that_is_there_and_says_what_it_reads():
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert len(spec["what"]) > 40
    readers = {"span_mean_s", "span_attr_ratio", "span_attr_per_op", "xla_module_s",
               "roofline"}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] in readers  # none of them new


def test_a_rehearsal_of_the_cell_fails_only_what_a_cpu_must(tmp_path):
    """One traced rehearsal through the benchmark's command, with the
    one-device route forced onto the CPU (auto routing takes the host engine
    here, whose count takes the rows route): every new host-side metric has
    a value, the reference passes, and the run is `correct: false` for want
    of a TPU and for the forced route, and nothing else."""
    import subprocess

    root = os.path.dirname(BENCH)
    proc = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "1", "--rows", "20000",
         "--cache-dir", str(tmp_path / "cache")],
        cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
                 KART_DIFF_DEVICE="1"),
        capture_output=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stderr.decode()[-2000:]
    result = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    failed = {name for name, ok in result["checks"].items() if not ok}
    # no TPU here, and a route forced (the test suite's eight virtual CPU
    # devices are not one chip either)
    assert failed - {"device_count"} == {"not_a_rehearsal", "platform_is_tpu",
                                         "auto_routing"}
    assert result["correct"] is False and result["failed"] == 0
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    host_side = [n for n in NEW_METRICS if not n.startswith("kernel.")]
    assert set(host_side) <= set(metrics)
    # at 20,000 rows a census block spans whole latitude bands: the bound is
    # coarse, and at or above 9% it sent the count to the changed-rows route
    assert 9.0 <= metrics["prefilter.bound_share"] <= 100.0
    assert 25.0 <= metrics["prefilter.changed_keep_share"] <= 34.0
    assert 0 < metrics["prefilter.envelope_read_share"] <= 100.0
    assert 0 <= metrics["refine.residue_share"] < 5.0
    assert metrics["prefilter.span_s"] >= (
        metrics["prefilter.census_s"] + metrics["prefilter.changed_s"]
    ) * 0.999
