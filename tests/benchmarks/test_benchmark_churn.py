"""The republish deployment (``baseline2_points_10m_churn``) at a small size
on the CPU: the builder's edit sets against the program through the CLI
(``kart diff -o json-lines`` names exactly the builder's inserts, updates and
deletes for both commits, on every route, in the host twin's bytes); the
windowed join's tile census on the builder's two key patterns against a
numpy recount; the two readers the cells brought, on hand-made span events;
the five new metrics on a traced run of each cell and on the parent's trace;
and the references' checks."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)

import costs  # noqa: E402
from test_benchmark_mesh_readers import read_metric, reference  # noqa: E402
from test_benchmark_span_readers import module, reader, span  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CHURN, BULK = "points10m.diff_count.churn", "points10m.diff_count.churn.bulk"
NEW_METRICS = {
    "join.dense_tile_share": [CHURN, BULK],
    "join.overflow_tiles": [CHURN, BULK],
    "kernel.join_window_s": [CHURN, BULK],
    "kernel.join_sort_s": [BULK],
    "kernel.join_roofline": [CHURN, BULK],
}
SEED = 2147483653  # past 32 signed bits, as the driver's are
WINDOW = "jit__classify_mergesort_core_window_split(11)"
SORT = "jit__classify_mergesort_core_split(12)"


def builder():
    """benchmarks/layers/int_pk_churn_layer.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_layers_int_pk_churn_layer",
        os.path.join(BENCH, "layers", "int_pk_churn_layer.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def jsonl_proof():
    """benchmarks/prove_churn_jsonl.py as a module (its line checker)."""
    spec = importlib.util.spec_from_file_location(
        "bench_prove_churn_jsonl", os.path.join(BENCH, "prove_churn_jsonl.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_params(rows, **changed):
    with open(os.path.join(BENCH, "configs", "baseline2_points_10m_churn.json")) as f:
        params = json.load(f)["layer"]["params"]
    return dict(params, rows=rows, **changed)


# -- the builder's edit sets ---------------------------------------------------

@pytest.mark.parametrize("rows", [3000, 40_000])
def test_edit_sets_are_counted_not_drawn_and_lie_where_the_config_says(rows):
    layer = builder()
    params = config_params(rows)
    sets = layer.edit_sets(params, SEED)
    again = layer.edit_sets(params, SEED)
    other = layer.edit_sets(params, SEED + 1)
    for branch in layer.BRANCHES:
        for mine, same, differs in zip(sets[branch][:2], again[branch][:2], other[branch][:2]):
            np.testing.assert_array_equal(mine, same)
            assert mine.shape == differs.shape and not np.array_equal(mine, differs)
    updated, deleted, inserted = sets["churn"]
    assert (len(updated), len(deleted), inserted) == (
        int(rows * 0.01), int(rows * 0.005), int(rows * 0.005)
    )
    both = np.concatenate([updated, deleted])
    assert len(np.unique(both)) == len(both) and both.min() >= 0 and both.max() < rows
    updated, deleted, inserted = sets["bulk"]
    assert (len(updated), len(deleted), inserted) == (
        int(rows * 0.01), int(rows * 0.005), 0
    )
    np.testing.assert_array_equal(deleted, np.arange(deleted[0], deleted[0] + len(deleted)))
    assert rows // 10 <= deleted[0] and deleted[-1] < rows - rows // 10
    assert not np.isin(updated, deleted).any() and len(np.unique(updated)) == len(updated)


def test_every_seeds_bulk_run_lies_strictly_inside_one_block_of_the_configs_size():
    """At the cell's own size, over 200 seeds: the run lies strictly inside
    one aligned block of ``bulk_block_rows`` rows (the chunk the configuration
    states), so no seed's hole straddles a chunk and goes unjoined by the
    sort-join; its start still reaches every block of the middle 80%."""
    layer = builder()
    params = config_params(10_000_000)
    block = params["bulk_block_rows"]
    blocks = set()
    for seed in range(SEED, SEED + 200):
        updated, deleted, _ = layer.bulk_edits(np.random.default_rng(seed), params)
        assert len(deleted) == 50_000 and (np.diff(deleted) == 1).all()
        first, last = int(deleted[0]), int(deleted[-1])
        assert first // block == last // block, seed
        assert first % block > 0 and last % block < block - 1, seed
        assert 1_000_000 <= first and last < 9_000_000
        assert len(updated) == 100_000 and not np.isin(updated, deleted).any()
        blocks.add(first // block)
    assert blocks == set(range(1, 9))


# -- (a) the builder against the program, through the CLI ----------------------

@pytest.fixture(scope="module")
def churn_repo(tmp_path_factory):
    layer = builder()
    params = config_params(6000)
    base = tmp_path_factory.mktemp("churn-base")
    layer.build_base(str(base), params)
    path, info = layer.add_edit_commit(
        str(base), str(tmp_path_factory.mktemp("churn-work")), params, SEED
    )
    return path, info, params


def kart(path, *args, env=None):
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    result = CliRunner().invoke(cli, ["-C", path, *args], env=env, catch_exceptions=False)
    assert result.exit_code == 0, result.output
    return result.stdout_bytes


ROUTES = {
    "auto": None,
    "host_twin": {"KART_DIFF_BACKEND": "host_native", "KART_DIFF_DEVICE": "0",
                  "KART_DIFF_SHARDED": "0"},
    "device_forced": {"KART_DIFF_DEVICE": "1"},
}


@pytest.mark.parametrize("branch", ["churn", "bulk"])
def test_jsonl_names_the_builders_inserts_updates_and_deletes(churn_repo, branch, tmp_path):
    path, info, params = churn_repo
    layer = builder()
    commit = info["commits"][branch]
    outputs = {
        route: kart(path, "diff", f"HEAD...{branch}", "-o", "json-lines", env=env)
        for route, env in ROUTES.items()
    }
    assert outputs["auto"] == outputs["host_twin"] == outputs["device_forced"]
    # every line against the builder's sets, by the full-size proof's own
    # checker; held against the other commit's sets it has to fail
    out = tmp_path / "diff.jsonl"
    out.write_bytes(outputs["auto"])
    checks = jsonl_proof().check_lines(str(out), commit)
    assert all(checks.values()), checks
    other = info["commits"][{"churn": "bulk", "bulk": "churn"}[branch]]
    wrong = jsonl_proof().check_lines(str(out), other)
    assert not wrong["names_the_updated_pks"] and not wrong["names_the_deleted_pks"]
    # one feature's value off by one: the pks still match, the values do not
    lines = outputs["auto"].splitlines()
    at = next(i for i, line in enumerate(lines) if b'"type":"feature"' in line.replace(b" ", b""))
    feature = json.loads(lines[at])
    side = feature["change"].get("+") or feature["change"]["-"]
    side["rating"] += 1.0
    lines[at] = json.dumps(feature).encode()
    out.write_bytes(b"\n".join(lines) + b"\n")
    tampered = jsonl_proof().check_lines(str(out), commit)
    assert not tampered["values_are_the_builders"]
    assert tampered["names_the_updated_pks"] and tampered["names_the_inserted_pks"]
    assert commit["rows"] == params["rows"] - len(commit["deleted_pks"]) + len(
        commit["inserted_pks"]
    )
    np.testing.assert_array_equal(
        commit["inserted_pks"],
        layer.PK_BASE + params["rows"] + np.arange(len(commit["inserted_pks"])),
    )


@pytest.mark.parametrize("cell", [CHURN, BULK])
def test_feature_count_passes_its_reference_and_fails_anothers(churn_repo, cell):
    path, info, _ = churn_repo
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == cell]
    with open(os.path.join(BENCH, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    argv = [a.format(repo=path, out="unused") for a in traffic["argv"]]
    assert argv[:2] == ["-C", path]
    output = kart(path, *argv[2:])
    checks = reference(traffic["reference"]).check(output, info)
    assert all(checks.values()), checks
    assert set(traffic["rehearsal_checks"]) <= set(checks)
    assert ("no_join_overflows" in checks) == (cell == CHURN)
    other = {CHURN: "bulk", BULK: "churn"}[cell]
    wrong = kart(path, "diff", f"HEAD...{other}", "-o", "feature-count")
    assert not reference(traffic["reference"]).check(wrong, info)["count_equals_edits"]
    assert not reference(traffic["reference"]).check(b"", info)["one_dataset_counted"]


def test_no_join_overflows_reads_the_programs_counter():
    from kart_tpu import telemetry as tm

    ref = reference("feature_count_churn")
    info = {"commits": {"churn": {"inserted_pks": [1], "updated_pks": [2, 3],
                                  "deleted_pks": []}}}
    tm.reset()
    tm.enable(metrics=True)
    try:
        assert ref.check(b"layer:\n\t3 features changed\n", info) == {
            "one_dataset_counted": True, "count_equals_edits": True,
            "no_join_overflows": True,
        }
        tm.incr("diff.device.join_overflows")
        assert ref.check(b"layer:\n\t3 features changed\n", info)["no_join_overflows"] is False
    finally:
        tm.reset()


# -- (b) the tile census on the builder's key patterns -------------------------

def key_pattern_blocks(branch, params):
    """(old, new) FeatureBlocks with the key columns the builder's commit
    ``branch`` gives the sidecars, as chip_smoke.py's ``churn`` phase makes
    them, and the commit's (updates, deletes, inserts)."""
    import chip_smoke

    layer = builder()
    updated, deleted, n_inserted = layer.edit_sets(params, SEED)[branch]
    old, new = chip_smoke._churn_blocks(layer, branch, params, SEED)
    return old, new, (len(updated), len(deleted), n_inserted)


@pytest.mark.parametrize("branch,changed,overflows", [
    ("churn", {}, False),
    # the cell's hole is 50,000 rows of 10M; at the test's size the fraction
    # is raised until the one hole outgrows a window (641 rows) as it does
    ("bulk", {"bulk_delete_frac": 0.04}, True),
    ("bulk", {"bulk_delete_frac": 0.02}, False),
])
def test_tile_census_on_the_builders_key_patterns(branch, changed, overflows, monkeypatch):
    from kart_tpu import runtime
    from kart_tpu import telemetry as tm
    from kart_tpu.ops.diff_kernel import (
        classify_blocks,
        classify_blocks_reference,
        join_census_reference,
    )

    old, new, (n_upd, n_del, n_ins) = key_pattern_blocks(
        branch, config_params(20_000, **changed)
    )
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        old_class, new_class, counts = classify_blocks(old, new)
        counters = tm.counters_snapshot()
        (attrs,) = [
            e["args"] for e in tm.drain_events() if e["name"] == "diff.device.kernel"
        ]
    finally:
        tm.reset()
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    assert counts == {"inserts": n_ins, "updates": n_upd, "deletes": n_del}
    dense, overflowing = join_census_reference(old, new)
    assert (attrs["dense_tiles"], attrs["overflow_tiles"]) == (dense, overflowing)
    assert (overflowing > 0) == overflows
    if not overflows:
        assert attrs["join"] == "window" and not attrs.get("window_ran", False)
        assert counters.get(("diff.device.join_overflows", ()), 0) == 0
    # a call that overflows is held to its answer (above) and its census,
    # not to the join the program then picks: that is the program's choice
    assert 0 < dense <= attrs["tiles"]
    assert counters.get(("diff.device.join_dense_tiles", ()), 0) == dense
    assert not [k for k in counters if k[0] == "diff.device.fallbacks"]


# -- (c) the readers and the metric files --------------------------------------

def kernel_span(t0, dur=0.08, **attrs):
    return span("diff.device.kernel", t0, dur, "diff.classify", program="mergesort",
                bucket=10_485_760, **attrs)


def command(t0, **kernel_attrs):
    return [
        kernel_span(t0 + 0.1, **kernel_attrs),
        span("diff.classify", t0, 0.3, "cli.command", rows=10_000_000,
             backend="device_jax"),
        span("cli.command", t0 - 0.02, 0.4),
    ]


def churn_run():
    """ctx of a traced run of three churn commands: the windowed join
    answers, 60,000 and 58,000 and 59,000 of 81,920 tiles dense."""
    dense = [60_000, 58_000, 59_000]
    return {
        "ops_events": [
            command(10.0 + i, join="window", tiles=81_920, dense_tiles=d, overflow_tiles=0)
            for i, d in enumerate(dense)
        ],
        "xla": [module(100.0 + i, 0.06, name=WINDOW) for i in range(3)],
        "ops_walls": [0.2] * 3, "device_kind": "TPU v5 lite",
    }


def bulk_run():
    """ctx of a traced run of two bulk commands: the windowed join runs
    0.025 s and overflows on 7 and 9 tiles, the sort-join answers in 0.775 s."""
    return {
        "ops_events": [
            command(10.0 + i, join="sort", window_ran=True, tiles=81_920,
                    dense_tiles=1_000, overflow_tiles=over)
            for i, over in enumerate([7, 9])
        ],
        "xla": [
            module(100.0 + i + at, dur, name=name) for i in range(2)
            for at, dur, name in ((0.0, 0.025, WINDOW), (0.03, 0.775, SORT))
        ],
        "ops_walls": [0.9] * 2, "device_kind": "TPU v5 lite",
    }


def parent_run():
    """The parent commit in a new cell: the same spans and programs, none of
    the attributes this PR adds."""
    return {
        "ops_events": [command(10.0, join="sort", tiles=81_920)],
        "xla": [module(100.0, 0.025, name=WINDOW), module(100.03, 0.775, name=SORT)],
        "ops_walls": [0.9], "device_kind": "TPU v5 lite",
    }


def test_ratio_reader_sums_both_attributes_over_all_traced_commands():
    read = reader("span_attr_ratio").read
    args = {"span": "diff.device.kernel", "numerator": "dense_tiles",
            "denominator": "tiles", "scale": 100.0}
    assert read(churn_run(), **args) == pytest.approx(100.0 * 177_000 / (3 * 81_920))
    # a span that lacks either attribute does not count, on either side
    ctx = churn_run()
    ctx["ops_events"][0].append(kernel_span(10.5, tiles=1_000_000))
    assert read(ctx, **args) == pytest.approx(100.0 * 177_000 / (3 * 81_920))
    assert read(parent_run(), **args) is None
    assert read({"ops_events": [command(1.0, tiles=0, dense_tiles=0)]}, **args) is None
    assert read({"ops_events": []}, **args) is None


def test_per_op_reader_is_the_mean_over_traced_commands_and_reads_zero_as_zero():
    read = reader("span_attr_per_op").read
    args = {"span": "diff.device.kernel", "attr": "overflow_tiles"}
    assert read(bulk_run(), **args) == pytest.approx(8.0)
    assert read(bulk_run(), scale=100.0, **args) == pytest.approx(800.0)
    assert read(churn_run(), **args) == 0.0
    # two kernel spans in one command add up; a command without one counts
    ctx = bulk_run()
    ctx["ops_events"][0].append(kernel_span(10.6, overflow_tiles=4))
    ctx["ops_events"].append([span("cli.command", 30.0, 0.1)])
    assert read(ctx, **args) == pytest.approx(20.0 / 3)
    assert read(parent_run(), **args) is None
    assert read({"ops_events": []}, **args) is None


def check_manifest(manifest):
    """The five the cells came with list their cells (a later PR may list
    another cell that runs the join beside them, and other metrics, of any
    layer, for a churn cell)."""
    listed = {
        m["name"]: m for m in manifest["per_layer"]
        if set(m.get("workloads", ())) & {CHURN, BULK}
    }
    assert set(NEW_METRICS) <= set(listed)
    for name, m in listed.items():
        assert m["moves"] == "diff_wall_s"
        if name in NEW_METRICS:
            assert set(NEW_METRICS[name]) <= set(m["workloads"]) and m["layer"] == "kernel"
    for cell in (CHURN, BULK):
        (entry,) = [w for w in manifest["workloads"] if w["name"] == cell]
        assert entry["chips"] == 1 and entry["config"] == "baseline2_points_10m_churn"


def test_new_metrics_are_listed_for_the_new_cells_alone():
    check_manifest(MANIFEST)


@pytest.mark.parametrize("name,want_churn,want_bulk", [
    ("join.dense_tile_share", 100.0 * 177_000 / 245_760, 100.0 * 1_000 / 81_920),
    ("join.overflow_tiles", 0.0, 8.0),
    ("kernel.join_window_s", 0.06, 0.025),
    ("kernel.join_sort_s", None, 0.775),
    ("kernel.join_roofline",
     100.0 * costs.least_seconds("classify_sort_join", "TPU v5 lite",
                                 rows_old=10_000_000, rows_new=10_000_000) / 0.06,
     100.0 * costs.least_seconds("classify_sort_join", "TPU v5 lite",
                                 rows_old=10_000_000, rows_new=10_000_000) / 0.8),
])
def test_new_metric_on_a_traced_run_of_each_cell(name, want_churn, want_bulk):
    for ctx, want in ((churn_run(), want_churn), (bulk_run(), want_bulk)):
        got = read_metric(name, ctx)
        if want is None:
            assert got is None  # no sort-join ran: the line leaves it out
        else:
            assert got == pytest.approx(want)
    assert 0 < read_metric("kernel.join_roofline", bulk_run()) < 100


@pytest.mark.parametrize("name", ["join.dense_tile_share", "join.overflow_tiles"])
def test_attribute_metric_says_nothing_of_the_parent_commit(name):
    assert read_metric(name, parent_run()) is None


@pytest.mark.parametrize("name", ["kernel.join_window_s", "kernel.join_sort_s",
                                  "kernel.join_roofline"])
def test_trace_metric_reads_the_parent_commit_too_and_nothing_without_a_trace(name):
    assert read_metric(name, parent_run()) > 0
    ctx = bulk_run()
    ctx["xla"] = []  # a CPU rehearsal: no device trace
    assert read_metric(name, ctx) is None


# -- the one-off full-size proof, rehearsed -------------------------------------

def test_jsonl_proof_rehearsal_holds_every_line_against_the_builder(tmp_path):
    """benchmarks/prove_churn_jsonl.py at a small size in a child process:
    every check of both commits but the engine's name is true, and without a
    TPU it ends ``ok: false`` with a non-zero exit code."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "prove_churn_jsonl.py"),
         "--seed", str(SEED), "--rows", "4000", "--cache-dir", str(tmp_path / "cache")],
        cwd=os.path.dirname(BENCH), env=env, capture_output=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stderr.decode()[-2000:]
    result = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert result["ok"] is False and result["device"]["platform"] == "cpu"
    assert set(result["commits"]) == {"churn", "bulk"}
    for branch, commit in result["commits"].items():
        failed = {name for name, ok in commit["checks"].items() if not ok}
        assert failed == {"backend"}, (branch, failed)
        assert commit["backend"] == ["host_native"] and commit["bytes"] > 0
    assert result["commits"]["churn"]["n_edits"] == 40 + 20 + 20
    assert result["commits"]["bulk"]["rows"] == 4000 - 20
    assert os.listdir(tmp_path) == ["cache"], "the proof left its working directory behind"
