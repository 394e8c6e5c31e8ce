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
    "prefilter.scan_s": "diff.prefilter.scan",
    "prefilter.propagate_s": "diff.prefilter.propagate",
    "prefilter.compact_s": "diff.prefilter.compact",
    "refine.span_s": "diff.refine",
}
NEW_METRICS = [
    *SPAN_METRICS, "prefilter.keep_share", "prefilter.scanned_block_share",
    "refine.residue_share", "kernel.filtered_classify_s",
    "kernel.filtered_classify_roofline",
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
    reads = [
        m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]
    ]
    assert set(NEW_METRICS) <= set(reads)  # a later PR may list more for this cell alone


# -- the builder's edit sets -------------------------------------------------------

@pytest.mark.parametrize("rows", [3000, 40_000])
def test_edit_rows_are_seeded_counted_and_sorted(rows):
    layer = builder()
    params = config_params(rows)
    picked = layer.edit_rows(params, SEED)
    assert len(picked) == max(1, int(rows * params["edit_frac"]))
    assert (np.diff(picked) > 0).all() and 0 <= picked[0] and picked[-1] < rows
    np.testing.assert_array_equal(picked, layer.edit_rows(params, SEED))
    assert not np.array_equal(picked, layer.edit_rows(params, SEED + 1))
    # away from the filter's edges the draw is the founding builder's
    founding = np.sort(
        np.random.default_rng(SEED).choice(rows, size=len(picked), replace=False)
    )
    np.testing.assert_array_equal(picked, founding)


def test_an_edited_point_near_a_filter_edge_is_drawn_again(monkeypatch):
    """With a clearance as wide as the layer's spacing some draws fall near
    an edge: they are replaced, the count and the seed's other rows stay."""
    layer = builder()
    params = config_params(40_000, edit_frac=0.05)
    founding = layer.edit_rows(params, SEED)
    monkeypatch.setattr(layer, "EDGE_CLEARANCE", 0.5)
    ring = layer.filter_ring(params)
    x, y = layer.base_layer.origins("POINT", layer.PK_BASE + founding, params["rows"])
    near = layer.edge_distance(ring, x, y) < 0.5
    assert near.any()
    picked = layer.edit_rows(params, SEED)
    assert len(picked) == len(founding) == len(set(picked.tolist()))
    assert set(founding[~near].tolist()) <= set(picked.tolist())
    x, y = layer.base_layer.origins("POINT", layer.PK_BASE + picked, params["rows"])
    assert (layer.edge_distance(ring, x, y) >= 0.5).all()


def test_edge_distance_is_the_distance_to_the_nearest_segment():
    layer = builder()
    square = np.array([(0, 0), (10, 0), (10, 10), (0, 10), (0, 0)], dtype=float)
    x = np.array([5.0, 5.0, -3.0, 13.0, 10.0])
    y = np.array([5.0, 1.0, 5.0, 14.0, 2.0])
    np.testing.assert_allclose(
        layer.edge_distance(square, x, y), [5.0, 1.0, 3.0, 5.0, 0.0]
    )


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

def command(t0, survivors=5_900_000, scanned=272, residue=3, candidates=59_000):
    """The span events of one traced filtered count that starts at ``t0``."""
    return [
        span("cli.command", t0, 0.30),
        span("diff.prefilter", t0 + 0.04, 0.08, "cli.command", rows=10_000_000),
        span("diff.prefilter.scan", t0 + 0.04, 0.02, "diff.prefilter",
             rows=20_000_000, blocks=4884, blocks_scanned=scanned,
             hits_old=survivors // 2, hits_new=survivors // 2),
        span("diff.prefilter.propagate", t0 + 0.06, 0.01, "diff.prefilter", probed=0),
        span("diff.prefilter.compact", t0 + 0.07, 0.05, "diff.prefilter",
             rows=20_000_000, survivors=survivors, bytes=survivors * 28, runs=50),
        span("diff.classify", t0 + 0.12, 0.06, "cli.command", rows=survivors // 2,
             backend="device_jax", counts_only=False),
        span("diff.refine", t0 + 0.18, 0.012, "cli.command", candidates=candidates,
             inside=37_000, outside=candidates - 37_000 - residue, residue=residue,
             blobs_read=residue),
    ]


def traced_run():
    return {
        "ops_events": [command(10.0), command(11.0, survivors=5_900_004, residue=5)],
        "xla": [module(100.0 + i, 0.0055, name=WINDOW) for i in range(2)],
        "ops_walls": [0.3, 0.3], "device_kind": "TPU v5 lite",
    }


def test_each_new_metric_reads_its_span_or_program():
    ctx = traced_run()
    durations = {"diff.prefilter": 0.08, "diff.prefilter.scan": 0.02,
                 "diff.prefilter.propagate": 0.01, "diff.prefilter.compact": 0.05,
                 "diff.refine": 0.012}
    for name, source in SPAN_METRICS.items():
        assert read_metric(name, ctx) == pytest.approx(durations[source]), name
    assert read_metric("prefilter.keep_share", ctx) == pytest.approx(
        100.0 * (5_900_000 + 5_900_004) / 40_000_000
    )
    assert read_metric("prefilter.scanned_block_share", ctx) == pytest.approx(
        100.0 * 272 / 4884
    )
    assert read_metric("refine.residue_share", ctx) == pytest.approx(100.0 * 8 / 118_000)
    assert read_metric("kernel.filtered_classify_s", ctx) == pytest.approx(0.0055)
    # 29 B a row over both sides of the survivors at 819 GB/s, over 0.0055 s
    least = (2_950_000 + 2_950_002) * 2 * 29 / 819e9
    assert read_metric("kernel.filtered_classify_roofline", ctx) == pytest.approx(
        100.0 * least / 0.011
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
    readers = {"span_mean_s", "span_attr_ratio", "xla_module_s", "roofline"}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] in readers  # none of them new


def test_a_rehearsal_of_the_cell_fails_only_what_a_cpu_must(tmp_path):
    """One traced rehearsal through run.py, as the driver runs it: every new
    host-side metric has a value, the reference passes, and the run is
    `correct: false` for want of a TPU and nothing else."""
    import subprocess

    root = os.path.dirname(BENCH)
    proc = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "1", "--rows", "20000",
         "--cache-dir", str(tmp_path / "cache")],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path)),
        capture_output=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stderr.decode()[-2000:]
    result = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    failed = {name for name, ok in result["checks"].items() if not ok}
    # no TPU here: the host engine answers, which is not the cell's backend
    # (and the test suite's eight virtual CPU devices are not one chip)
    assert failed - {"device_count"} == {"not_a_rehearsal", "platform_is_tpu", "backend"}
    assert result["correct"] is False and result["failed"] == 0
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    host_side = [n for n in NEW_METRICS if not n.startswith("kernel.")]
    assert set(host_side) <= set(metrics)
    assert 25.0 <= metrics["prefilter.keep_share"] <= 34.0
    assert 0 < metrics["prefilter.scanned_block_share"] <= 100.0
    assert 0 <= metrics["refine.residue_share"] < 5.0
    assert metrics["prefilter.span_s"] >= (
        metrics["prefilter.scan_s"] + metrics["prefilter.propagate_s"]
        + metrics["prefilter.compact_s"]
    ) * 0.999
