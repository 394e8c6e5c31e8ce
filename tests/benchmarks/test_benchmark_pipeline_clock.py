"""The pipelined classify on the device trace's clock (ISSUE 37): hand-made
traced commands of the pipeline as PR 36 left it — a chunk's program starts
when its inputs land, a chunk's copy before the ``diff.device.kernel`` span
that waits for it opens — with the two clock pings around them, and a device
trace at a known offset. The pings give the offset back with residual 0 and
a width equal to the launch and notice put in; the old anchor over the same
events does not; the idle seconds of the stages add up to the whole."""

import json
import os
import sys

import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import span_tree  # noqa: E402
from test_benchmark_span_readers import (  # noqa: E402
    UNIX, metric_spec, module, reader, span,
)

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELLS = ["points10m.diff_count", "polygons10m.diff_jsonl", "nodes10m.diff_count.filtered"]
MESH = "points10m.diff_count.mesh4"
PROBE = "jit__clock_probe(7)"
WINDOW = "jit__classify_mergesort_core_window_split(11)"
CLOCK = {"anchor_span": "diff.device.clock", "module_prefix": "jit__clock_probe"}
OLD = {"anchor_span": "diff.device.kernel", "module_prefix": "jit__classify_"}
STAGES = ["pack", "enqueue", "transfer", "kernel", "fetch"]
IDLE_STAGES = [f"pipeline.idle_{stage}_s" for stage in STAGES]
NEW_METRICS = [
    "trace.clock_residual_s", "trace.clock_width_s", "pipeline.idle_s", *IDLE_STAGES,
    "classify.enqueue_s", "pipeline.hidden_program_share",
    "pipeline.landed_ahead_share",
]
# seconds, as a traced run reads them: the profiler slows the copy tenfold
PLAN, PACK, ENQUEUE, COPY, WAIT, FETCH, PROGRAM, PING = (
    0.004, 0.0001, 0.003, 0.07, 0.0001, 0.0003, 0.0015, 0.000002,
)


def read_metric(name, ctx):
    spec = metric_spec(name)
    return reader(spec["reader"]).read(ctx, **spec["args"])


def pipelined_command(t0, chunks=4, launch=0.0004, notice=0.0006, hidden=None):
    """One traced count command that starts at program time ``t0``, the
    spans in the order ``classify_blocks_streamed`` opens them -> (events,
    [(program start, seconds, name)] in program time). Chunk c's program
    starts when its ``diff.device.transfer`` span ends: the chunk before is
    drained under it, and its own kernel span opens a chunk's copy later,
    when it has long ended. Only the last two chunks' spans wait for their
    programs: one is drained right after it landed, the other is called
    and waited for with nothing left to copy."""
    events, programs, ends = [], [], {}
    t = t0 + 0.02  # the CLI before the classify
    classify_t0 = t

    def add(name, dur, **args):
        nonlocal t
        events.append(span(name, t, dur, "diff.classify", **args))
        t += dur

    def ping(at):
        programs.append((t + launch, PING, PROBE))
        add("diff.device.clock", launch + PING + notice, at=at)

    def landed(c, lag=0.0):
        add("diff.device.transfer", COPY, chunk=c, bytes=58_720_256, ready=0)
        programs.append((t + lag, PROGRAM, WINDOW))
        ends[c] = t + lag + PROGRAM

    def drain(c):
        waited = ends[c] + notice - t
        add("diff.device.kernel", max(waited, WAIT), chunk=c,
            program="window_join", ready=int(waited <= 0))
        add("diff.device.fetch", FETCH, chunk=c, bytes=2_097_176)

    t += PLAN  # routing and the chunk plan: diff.classify's own time
    ping("start")
    for c in range(chunks):
        add("diff.device.pack", PACK, chunk=c, bytes=0)
        add("diff.device.enqueue", ENQUEUE, chunk=c, bytes=58_720_256)
        if c >= 1:
            landed(c - 1)
        if c >= 2:
            drain(c - 2)
    drain(chunks - 2)
    # the last program runs inside its kernel span, a launch after it opens
    landed(chunks - 1, lag=launch)
    drain(chunks - 1)
    ping("end")
    t += PLAN  # the class arrays handed back
    n_hidden = chunks - 2 if hidden is None else hidden
    events.append(span(
        "diff.classify", classify_t0, t - classify_t0, "cli.command",
        backend="device_jax", chunks=chunks, view_chunks=chunks - 1,
        hidden_programs=n_hidden, landed_ahead=1,
    ))
    events.append(span("cli.command", t0, t + 0.03 - t0))
    events.append({"name": "kart_trace_epoch", "ph": "M", "tid": 0, "args": {"unix": UNIX}})
    return events, programs


def traced_run(offset, starts=(10.0, 20.0, 30.0), **kwargs):
    """ctx of a traced run: the device trace's clock reads the program's
    unix time plus ``offset``."""
    ops_events, xla = [], []
    for t0 in starts:
        events, programs = pipelined_command(t0, **kwargs)
        ops_events.append(events)
        xla += [module(UNIX + m + offset, dur, name=name) for m, dur, name in programs]
    return {"ops_events": ops_events, "xla": xla, "ops_walls": [1.0] * len(starts)}


@pytest.mark.parametrize("offset", [0.0, 3.0, -UNIX + 0.25])
@pytest.mark.parametrize("launch,notice", [(0.0004, 0.0006), (0.002, 0.0001)])
def test_the_pings_give_the_offset_back_where_the_old_anchor_cannot(offset, launch, notice):
    ctx = traced_run(offset, launch=launch, notice=notice)
    got, residual = span_tree.clock_offset(
        ctx["ops_events"], ctx["xla"], CLOCK["anchor_span"], CLOCK["module_prefix"]
    )
    assert residual == 0.0
    # the middle of [true - notice, true + launch]
    assert got == pytest.approx(offset + (launch - notice) / 2, abs=2e-6)
    assert read_metric("trace.clock_residual_s", ctx) == 0.0
    assert read_metric("trace.clock_width_s", ctx) == pytest.approx(
        launch + notice, abs=2e-6
    )
    # the old anchor pairs each chunk's kernel span with its program: most
    # programs began a copy, a pack, an enqueue and what was drained in
    # between before their span opened, the last two are waited for inside
    # theirs, and no one offset suits both kinds
    lag = COPY + PACK + ENQUEUE + WAIT + FETCH
    old = reader("align_residual").read(ctx, **OLD)
    assert old == pytest.approx((lag - notice) / 2, abs=2e-6)
    assert old > 30 * (launch + notice) / 2
    assert read_metric("trace.align_residual_s", ctx) == old


def test_the_width_is_the_shortest_launch_plus_the_shortest_notice_of_any_ping():
    """Each bound comes from its own best ping, over all commands."""
    ctx = traced_run(3.0, starts=(10.0,), launch=0.002, notice=0.0002)
    events, programs = pipelined_command(20.0, launch=0.0003, notice=0.004)
    ctx["ops_events"].append(events)
    ctx["xla"] += [module(UNIX + m + 3.0, dur, name=name) for m, dur, name in programs]
    assert read_metric("trace.clock_width_s", ctx) == pytest.approx(0.0005, abs=2e-6)
    assert read_metric("trace.clock_residual_s", ctx) == 0.0


def test_no_width_without_a_probe_a_ping_and_none_where_no_offset_fits():
    read = reader("align_width").read
    ctx = traced_run(3.0)
    ctx["xla"].remove(next(e for e in ctx["xla"] if e["name"] == PROBE))
    assert read(ctx, **CLOCK) is None  # the profiler lost one
    assert read({**traced_run(3.0), "xla": []}, **CLOCK) is None  # a CPU rehearsal
    assert read(traced_run(3.0), "diff.nowhere", "jit__clock_probe") is None
    # the old anchor's interval does not exist: width 0, the residual says why
    assert read(traced_run(3.0), **OLD) == 0.0


@pytest.mark.parametrize("chunks", [3, 4, 10])
def test_the_idle_seconds_of_the_stages_add_up_to_the_pipelines(chunks):
    launch = notice = 0.0005  # so that the offset's middle is the true one
    ctx = traced_run(3.0, chunks=chunks, launch=launch, notice=notice)
    got = {name: read_metric(name, ctx) for name in ["pipeline.idle_s", *IDLE_STAGES]}
    assert all(v is not None and v >= 0 for v in got.values())
    idle = reader("idle_under_span").read
    pings = idle(ctx, span="diff.device.clock", **CLOCK)
    assert pings == pytest.approx(2 * (launch + notice), abs=4e-6)
    # no program runs in diff.classify's own time (before the first ping,
    # after the last)
    assert got["pipeline.idle_s"] == pytest.approx(
        sum(got[name] for name in IDLE_STAGES) + pings + 2 * PLAN, abs=1e-5
    )
    classify = [e for e in ctx["ops_events"][0] if e["name"] == "diff.classify"][0]
    busy = chunks * PROGRAM + 2 * PING
    assert got["pipeline.idle_s"] == pytest.approx(classify["dur"] / 1e6 - busy, abs=1e-5)
    # a hidden program's kernel span is open while the next chunk's program
    # runs; the last two spans wait for their own, then for its notice
    assert got["pipeline.idle_kernel_s"] == pytest.approx(launch + 2 * notice, abs=1e-5)
    # every copy but the first has the program of the chunk before under it
    assert got["pipeline.idle_transfer_s"] <= chunks * COPY + 1e-9
    assert got["pipeline.idle_transfer_s"] >= chunks * COPY - (chunks - 1) * PROGRAM
    assert got["pipeline.idle_transfer_s"] >= 0.9 * got["pipeline.idle_s"]


def test_the_counted_shares_and_the_enqueue_span():
    ctx = traced_run(3.0, starts=(10.0, 20.0), chunks=10, hidden=8)
    ctx["ops_events"].append(pipelined_command(30.0, chunks=10, hidden=9)[0])
    assert read_metric("pipeline.hidden_program_share", ctx) == pytest.approx(
        100.0 * 25 / 30
    )
    assert read_metric("pipeline.landed_ahead_share", ctx) == pytest.approx(10.0)
    assert read_metric("classify.enqueue_s", ctx) == pytest.approx(10 * ENQUEUE)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_is_silent_on_the_parents_trace(name):
    """The parent's pipeline has the enqueue span and nothing else of this:
    no pings, no ``jit__clock_probe``, no counts on ``diff.classify``. A
    call of one chunk has no enqueue span either. Nothing raises."""
    ctx = traced_run(3.0)
    parent = {
        "ops_events": [
            [
                {k: v for k, v in e.items() if k != "args"} | {"args": {
                    k: v for k, v in e.get("args", {}).items()
                    if k not in ("ready", "hidden_programs", "landed_ahead")
                }}
                for e in events if e["name"] != "diff.device.clock"
            ]
            for events in ctx["ops_events"]
        ],
        "xla": [e for e in ctx["xla"] if e["name"] != PROBE],
        "ops_walls": ctx["ops_walls"],
    }
    value = read_metric(name, parent)
    assert (value is None) == (name != "classify.enqueue_s")
    one_chunk = {**parent, "ops_events": [
        [e for e in events if e["name"] != "diff.device.enqueue"]
        for events in parent["ops_events"]
    ]}
    assert read_metric(name, one_chunk) is None
    assert read_metric(name, {"ops_events": [[]], "xla": [], "ops_walls": [1.0]}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_is_a_file_over_a_reader_and_lists_the_three_cells(name):
    """``CELLS`` are the cells the metrics came with; a later PR may list a
    metric for more one-chip cells, never for the mesh cell (its root has no
    clock pings)."""
    spec = metric_spec(name)
    assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert len(spec["what"]) > 40
    # one new reader; every other metric is a file over code that was there
    assert (spec["reader"] == "align_width") == (name == "trace.clock_width_s")
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert set(CELLS) <= set(entry["workloads"]) and MESH not in entry["workloads"]
    assert entry["moves"] == "diff_wall_s" and "bound" not in entry
    overlaid = name.startswith(("pipeline.idle_", "trace."))
    assert entry["source"] == ("device_trace" if overlaid else "program_span")
    assert entry["layer"] == ("device" if name.startswith("trace.") else "classify")
    if overlaid:
        assert {k: spec["args"][k] for k in CLOCK} == CLOCK
        # the module name the program gives its probe
        from kart_tpu.ops.diff_kernel import _clock_probe

        assert CLOCK["module_prefix"] == "jit_" + _clock_probe.__wrapped__.__name__


def test_the_new_metrics_are_listed_once_each_and_not_for_the_mesh_cell():
    """By name, not by place: where an entry stands in ``per_layer`` is the
    driver's to hold (it compares entries by place, so a PR appends), and a
    test that held the tail would refuse the next appended metric."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert [names.count(name) for name in NEW_METRICS] == [1] * len(NEW_METRICS)
    mesh = [m["name"] for m in MANIFEST["per_layer"] if MESH in m.get("workloads", [MESH])]
    assert not set(mesh) & set(NEW_METRICS)
