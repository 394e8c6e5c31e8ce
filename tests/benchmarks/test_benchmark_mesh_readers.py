"""The readers the four-chip cell brought, on hand-made span events and
hand-made device-trace events of four planes with known answers: a roofline
over all the planes that ran a program, the seconds of the collectives on the
``XLA Ops`` line, the slowest plane; the mesh path's span metrics on a traced
mesh command; and the cell's reference, which is true only of a classify over
four shards. Every reader answers None on the trace of a program from before
the spans and the program's name existed."""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import device_planes  # noqa: E402
import reduce  # noqa: E402
from test_benchmark_span_readers import metric_spec, reader, span  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL = "points10m.diff_count.mesh4"
MESH_METRICS = [
    m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())
]
SPAN_METRICS = [
    "mesh.classify_s", "mesh.splits_s", "mesh.pack_s", "mesh.transfer_s",
    "mesh.dispatch_s", "mesh.drain_s", "mesh.self_s",
]
TRACE_METRICS = [
    "kernel.mesh_classify_s", "kernel.mesh_classify_roofline",
    "mesh.collective_s", "mesh.shard_max_s",
]
PLANES = [f"/device:TPU:{i}" for i in range(4)]
PROGRAM = "jit__mesh_classify(41)"
ROWS, ROUNDS, COMMANDS = 10_000_000, 3, 2
ROOT = "diff.device.classify"


def reference(name):
    """benchmarks/references/<name>.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_references_" + name, os.path.join(BENCH, "references", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metric(name, ctx):
    spec = metric_spec(name)
    return reader(spec["reader"]).read(ctx, **spec["args"])


def device_event(line, name, start, dur, plane):
    return {"plane": plane, "line": line, "name": name, "start": start, "dur": dur}


def mesh_command(t0):
    """The span events of one traced mesh command that starts at ``t0``:
    splits 4 ms, then three rounds of pack 10 / transfer 3 / dispatch 1 ms,
    each but the first followed by a 2 ms drain, a last drain of 5 ms, and
    1 ms a round that no child covers."""
    events, t = [span("diff.device.splits", t0, 0.004, ROOT, chunks=12)], t0 + 0.004
    for r in range(ROUNDS):
        t += 0.001
        events.append(span("diff.device.pack", t, 0.010, ROOT, round=r, bytes=100))
        events.append(span("diff.device.transfer", t + 0.010, 0.003, ROOT, round=r, bytes=100))
        events.append(span("diff.device.kernel", t + 0.013, 0.001, ROOT, round=r,
                           program="mesh_classify"))
        t += 0.014
        if r:
            events.append(span("diff.device.fetch", t, 0.002, ROOT, round=r - 1, bytes=24))
            t += 0.002
    events.append(span("diff.device.fetch", t, 0.005, ROOT, round=ROUNDS - 1, bytes=24))
    t += 0.005
    events.append(span(ROOT, t0, t - t0, "diff.classify", rows=ROWS, shards=4,
                       rounds=ROUNDS, bytes=100 * ROUNDS))
    events.append(span("diff.classify", t0 - 0.01, t - t0 + 0.02, "cli.command",
                       rows=ROWS, backend="sharded_jax"))
    events.append(span("cli.command", t0 - 0.05, t - t0 + 0.1))
    return events


def mesh_run():
    """ctx of a traced run of two mesh commands on four planes. A command,
    on each plane: three runs of the program, 40 ms each and 10 ms more on
    plane 3 (the slowest shard), each holding an all-reduce of 1 ms (2 ms on
    plane 0, which waits for plane 3) and a sort of 20 ms."""
    xla, ops_events = [], []
    for c in range(COMMANDS):
        ops_events.append(mesh_command(10.0 + c))
        for p, plane in enumerate(PLANES):
            for r in range(ROUNDS):
                start = 100.0 + c + 0.06 * r
                dur = 0.050 if p == 3 else 0.040
                xla.append(device_event(reduce.MODULES_LINE, PROGRAM, start, dur, plane))
                xla.append(device_event(
                    reduce.OPS_LINE, "%sort.16 = (u32[131072]) sort(...)", start, 0.020, plane))
                xla.append(device_event(
                    reduce.OPS_LINE, "%all-reduce.3 = s64[3]{0} all-reduce(%fusion.9)",
                    start + 0.03, 0.002 if p == 0 else 0.001, plane))
            # another program on the same plane is nobody's mesh classify
            xla.append(device_event(reduce.MODULES_LINE, "jit__step(7)", 200.0 + c, 0.5, plane))
    return {"ops_events": ops_events, "xla": xla, "ops_walls": [0.7] * COMMANDS,
            "device_kind": "TPU v5 lite"}


def parent_run():
    """The parent commit in the new cell: the route and its root, pack and
    transfer spans exist (without bytes), the program is called jit__step,
    and there is neither a splits, a kernel nor a fetch span."""
    events = [
        span("diff.device.pack", 10.0, 0.01, ROOT, round=0),
        span("diff.device.transfer", 10.01, 0.003, ROOT, round=0),
        span(ROOT, 10.0, 0.02, "diff.classify", rows=ROWS, shards=4, rounds=1),
        span("diff.classify", 9.99, 0.04, "cli.command", rows=ROWS, backend="sharded_jax"),
        span("cli.command", 9.9, 0.2),
    ]
    xla = [
        device_event(line, name, 100.0, 0.04, plane)
        for plane in PLANES
        for line, name in ((reduce.MODULES_LINE, "jit__step(7)"),
                           (reduce.OPS_LINE, "%sort.16 = (u32[131072]) sort(...)"))
    ]
    return {"ops_events": [events], "xla": xla, "ops_walls": [0.7],
            "device_kind": "TPU v5 lite"}


# -- device_planes ------------------------------------------------------------

def test_seconds_by_plane_keeps_the_planes_apart_and_the_idle_ones_at_zero():
    ctx = mesh_run()
    by_plane = device_planes.module_seconds_by_plane(ctx["xla"], "jit__mesh_classify")
    assert sorted(by_plane) == PLANES
    assert by_plane[PLANES[0]] == pytest.approx(COMMANDS * ROUNDS * 0.040)
    assert by_plane[PLANES[3]] == pytest.approx(COMMANDS * ROUNDS * 0.050)
    # a plane that has the line and no such program is there, at 0.0
    ctx["xla"].append(device_event(reduce.MODULES_LINE, "jit_other(1)", 0.0, 1.0,
                                   "/device:TPU:4"))
    assert device_planes.module_seconds_by_plane(ctx["xla"], "jit__mesh_")[
        "/device:TPU:4"] == 0.0
    assert device_planes.module_seconds_by_plane([], "jit__mesh_classify") == {}
    ops = device_planes.op_seconds_by_plane(ctx["xla"], "all-reduce")
    assert ops[PLANES[0]] == pytest.approx(COMMANDS * ROUNDS * 0.002)
    assert ops[PLANES[1]] == pytest.approx(COMMANDS * ROUNDS * 0.001)


# -- the three new readers ----------------------------------------------------

def test_mesh_kernel_seconds_are_the_mean_over_planes_per_command():
    # (3 planes x 0.12 s + one x 0.15 s) / 4 a command
    assert read_metric("kernel.mesh_classify_s", mesh_run()) == pytest.approx(
        (3 * 0.120 + 0.150) / 4
    )


def test_slowest_plane_is_the_largest_planes_seconds_per_command():
    ctx = mesh_run()
    assert read_metric("mesh.shard_max_s", ctx) == pytest.approx(ROUNDS * 0.050)
    assert read_metric("mesh.shard_max_s", ctx) > read_metric("kernel.mesh_classify_s", ctx)


def test_collective_seconds_are_the_all_reduce_ops_mean_over_planes():
    ctx = mesh_run()
    # a command: three all-reduces, 2 ms on one plane and 1 ms on three
    assert read_metric("mesh.collective_s", ctx) == pytest.approx(
        ROUNDS * (0.002 + 3 * 0.001) / 4
    )
    # async pairs count too, a sort does not
    ctx["xla"].append(device_event(
        reduce.OPS_LINE, "%all-reduce-start.1 = ...", 0.0, 0.004 * COMMANDS, PLANES[0]))
    assert read_metric("mesh.collective_s", ctx) == pytest.approx(
        ROUNDS * (0.002 + 3 * 0.001) / 4 + 0.004 / 4
    )
    assert reader("xla_op_s").read(ctx, prefix="all-gather") is None


def test_roofline_over_the_planes_that_ran_the_program():
    """29 B a row, both sides, over the peak of four chips, over the mean of
    the four planes' seconds: a quarter of what one chip's peak would give,
    and the same whether read per command or over the run."""
    ctx = mesh_run()
    least_one_chip = costs.least_seconds(
        "classify_sort_join", "TPU v5 lite", rows_old=ROWS, rows_new=ROWS
    )
    assert least_one_chip == pytest.approx(2 * ROWS * 29 / 819e9)
    mean_s = read_metric("kernel.mesh_classify_s", ctx)
    got = read_metric("kernel.mesh_classify_roofline", ctx)
    assert got == pytest.approx(100.0 * (least_one_chip / 4) / mean_s)
    assert 0 < got < 1
    # a fifth plane that never ran the program changes nothing: the peak is
    # that of the planes that ran it, and so is the time
    ctx["xla"].append(device_event(reduce.MODULES_LINE, "jit_other(1)", 0.0, 1.0,
                                   "/device:TPU:4"))
    assert read_metric("kernel.mesh_classify_roofline", ctx) == pytest.approx(got)
    # on one plane alone it is the one-chip roofline of the same program
    one = dict(ctx, xla=[e for e in ctx["xla"] if e["plane"] == PLANES[0]])
    assert read_metric("kernel.mesh_classify_roofline", one) == pytest.approx(
        100.0 * least_one_chip / (ROUNDS * 0.040)
    )
    with pytest.raises(KeyError):
        read_metric("kernel.mesh_classify_roofline", dict(ctx, device_kind="TPU v9"))


# -- the span metrics of the mesh path ----------------------------------------

def test_the_stages_and_the_self_time_add_up_to_the_root():
    ctx = mesh_run()
    got = {name: read_metric(name, ctx) for name in SPAN_METRICS}
    assert got["mesh.splits_s"] == pytest.approx(0.004)
    assert got["mesh.pack_s"] == pytest.approx(ROUNDS * 0.010)
    assert got["mesh.transfer_s"] == pytest.approx(ROUNDS * 0.003)
    assert got["mesh.dispatch_s"] == pytest.approx(ROUNDS * 0.001)
    assert got["mesh.drain_s"] == pytest.approx((ROUNDS - 1) * 0.002 + 0.005)
    assert got["mesh.self_s"] == pytest.approx(ROUNDS * 0.001)
    assert sum(v for k, v in got.items() if k != "mesh.classify_s") == pytest.approx(
        got["mesh.classify_s"]
    )


def test_every_new_metric_is_listed_for_the_mesh_cell_alone():
    """The mesh metrics list the mesh cell (a later PR may list more metrics
    for it, and another cell beside it); the one-chip kernel's two never
    list it (the mesh runs another program)."""
    assert set(SPAN_METRICS + TRACE_METRICS) <= set(MESH_METRICS)
    for name in ("kernel.classify_s", "kernel.classify_roofline"):
        (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]


@pytest.mark.parametrize("name", SPAN_METRICS + TRACE_METRICS)
def test_reader_finds_something_in_a_traced_mesh_run(name):
    value = read_metric(name, mesh_run())
    assert value is not None and value > 0


@pytest.mark.parametrize(
    "name",
    ["mesh.splits_s", "mesh.dispatch_s", "mesh.drain_s"] + TRACE_METRICS,
)
def test_reader_says_nothing_of_the_parent_commit(name):
    """What this record added is not in the parent's traces: the metric is
    left out of its line, and nothing raises. (The root, pack and transfer
    spans were there before, and their readers find them.)"""
    assert read_metric(name, parent_run()) is None


@pytest.mark.parametrize("name", ["mesh.classify_s", "mesh.pack_s", "mesh.transfer_s",
                                  "mesh.self_s"])
def test_reader_of_an_older_span_reads_the_parent_commit_too(name):
    assert read_metric(name, parent_run()) > 0


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_reader_says_nothing_without_a_device_trace(name):
    assert read_metric(name, dict(mesh_run(), xla=[])) is None


# -- the reference ------------------------------------------------------------

@pytest.mark.parametrize("shards,want", [(4, True), (2, False), (None, False)])
def test_shards_is_4_only_when_the_classify_ran_on_four_shards(shards, want):
    """The reference's own check: the count can be right on any engine;
    ``shards_is_4`` is true only when the mesh classify of the command just
    run went over four shards, and false for a host answer (no gauge)."""
    import jax

    from kart_tpu import telemetry
    from kart_tpu.diff.device_batch import classify_blocks_batched
    from kart_tpu.ops.diff_kernel import classify_blocks_host
    from kart_tpu.parallel.mesh import make_mesh
    from kart_tpu.parallel.sharded_diff import synthetic_block

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    old, new = synthetic_block(2000, seed=5), synthetic_block(2000, seed=5)
    new.oids = new.oids.copy()
    new.oids[3:2000:100, 0] ^= 1
    telemetry.reset()
    telemetry.enable(metrics=True)
    try:
        if shards is None:
            counts = classify_blocks_host(old, new)[2]
        else:
            counts = classify_blocks_batched(
                old, new, mesh=make_mesh(shards), batch_rows=256, counts_only=True
            )[2]
        n = sum(counts.values())
        checks = reference("feature_count_mesh4").check(
            b"synth: %d features changed\n" % n, {"n_edits": 20}
        )
    finally:
        telemetry.reset()
    assert checks == {
        "one_dataset_counted": True, "count_equals_edits": True, "shards_is_4": want,
    }
