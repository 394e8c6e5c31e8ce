"""The span readers on hand-made Chrome events and hand-made device-trace
events with known answers: self time by ``args.parent``, a rate from a
span's own count, idle time under a span with the two clocks laid over each
other, and the residual of that overlay. Every reader answers None on the
trace of a program from before the spans existed."""

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

import reduce  # noqa: E402
import span_tree  # noqa: E402

PLANE = "/device:TPU:0"
KERNEL = "jit__classify_mergesort_core(123)"
ANCHOR = {"anchor_span": "diff.device.kernel", "module_prefix": "jit__classify_"}
MAIN, WORKER = 1, 2
UNIX = 1790000000.0  # the program's ts = 0, in unix seconds


def reader(name):
    """benchmarks/readers/<name>.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_" + name, os.path.join(BENCH, "readers", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def metric_args(name):
    return metric_spec(name)["args"]


def span(name, start, dur, parent=None, tid=MAIN, **args):
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": start * 1e6, "dur": dur * 1e6,
            "tid": tid, "args": args}


def module(start, dur, name=KERNEL, plane=PLANE):
    return {"plane": plane, "line": reduce.MODULES_LINE, "name": name,
            "start": start, "dur": dur}


def command(t0, launch=0.005, notice=0.005, kernel=0.89, bytes_=600_000_000):
    """One traced `kart diff` that starts at program time ``t0``: the events
    of its trace file, and when its kernel ran (program time)."""
    k0 = t0 + 0.65
    k1 = k0 + launch + kernel + notice
    events = [
        {"name": "thread_name", "ph": "M", "tid": MAIN, "args": {"name": "MainThread"}},
        span("sidecar.load", t0 + 0.01, 0.002, "cli.command"),
        span("diff.device.pack", t0 + 0.1, 0.4, "diff.classify", bytes=bytes_),
        span("diff.device.transfer", t0 + 0.5, 0.15, "diff.classify", bytes=bytes_),
        span("diff.device.kernel", k0, k1 - k0, "diff.classify"),
        span("diff.device.fetch", k1, 0.02, "diff.classify", bytes=20_000_000),
        span("diff.changed_indices", k1 + 0.02, 0.03, "diff.classify"),
        span("diff.classify", t0 + 0.09, k1 + 0.06 - (t0 + 0.09), "cli.command"),
        span("serialise.chunk", k1 + 0.1, 0.5, "serialise.features"),
        span("odb.read_blobs_ordered", k1 + 0.1, 0.3, tid=WORKER),
        span("serialise.features", k1 + 0.08, 1.0, "cli.command"),
        span("cli.command", t0, k1 + 1.1 - t0),
        {"name": "kart_trace_epoch", "ph": "M", "tid": 0, "args": {"unix": UNIX}},
    ]
    return events, (k0 + launch, kernel)


def traced_run(offset, starts=(10.0, 20.0, 30.0), **kwargs):
    """ctx of a traced run: the device trace's clock reads the program's
    unix time plus ``offset``."""
    ops_events, xla = [], []
    for t0 in starts:
        events, (m0, dur) = command(t0, **kwargs)
        ops_events.append(events)
        xla.append(module(UNIX + m0 + offset, dur))
    return {"ops_events": ops_events, "xla": xla, "ops_walls": [3.0] * len(starts)}


def old_program_run():
    """A traced run of a program from before this record: no root, no
    parents, none of the stage spans."""
    events = [
        span("sidecar.load", 10.01, 0.002),
        span("diff.classify", 10.09, 1.6, rows=10, backend="device_jax"),
        {"name": "kart_trace_epoch", "ph": "M", "tid": 0, "args": {"unix": UNIX}},
    ]
    return {"ops_events": [events], "xla": [module(5.0, 0.89)], "ops_walls": [2.0]}


# -- span_self_s --------------------------------------------------------------

def test_self_time_is_the_span_less_its_children_on_its_thread():
    events, _ = command(10.0)
    classify = [e for e in events if e["name"] == "diff.classify"][0]
    children = 0.4 + 0.15 + 0.9 + 0.02 + 0.03
    want = classify["dur"] / 1e6 - children
    assert span_tree.self_seconds(events, "diff.classify") == pytest.approx(want)
    # a grandchild and a span on another thread take nothing off the root
    root = [e for e in events if e["name"] == "cli.command"][0]
    want_root = root["dur"] / 1e6 - 0.002 - classify["dur"] / 1e6 - 1.0
    assert span_tree.self_seconds(events, "cli.command") == pytest.approx(want_root)
    # the same name on another thread is not this span's child
    events.append(span("diff.device.pack", 10.2, 0.1, "diff.classify", tid=WORKER))
    assert span_tree.self_seconds(events, "diff.classify") == pytest.approx(want)


def test_self_time_reader_is_the_mean_over_traced_commands():
    ctx = traced_run(0.0)
    # one command names 5 ms more of its classify: a child in a gap
    ctx["ops_events"][1].append(span("diff.routing", 20.092, 0.005, "diff.classify"))
    one = span_tree.self_seconds(ctx["ops_events"][0], "diff.classify")
    got = reader("span_self_s").read(ctx, **metric_args("classify.self_s"))
    assert got == pytest.approx((3 * one - 0.005) / 3)
    assert reader("span_self_s").read(ctx, span="diff.prefilter") is None


# -- span_rate ----------------------------------------------------------------

def test_rate_is_the_spans_own_count_over_its_seconds():
    ctx = traced_run(0.0, starts=(10.0, 20.0))
    ctx["ops_events"][1].append(
        span("diff.device.transfer", 22.9, 0.05, "diff.classify", bytes=200_000_000)
    )
    args = metric_args("transfer.h2d_gbps")
    assert args["span"] == "diff.device.transfer" and args["attr"] == "bytes"
    # 1.4 GB in 0.35 s
    assert reader("span_rate").read(ctx, **args) == pytest.approx(4.0)
    assert reader("span_rate").read(ctx, "diff.device.kernel", "bytes", 1e-9) is None
    assert reader("span_rate").read(ctx, "diff.nowhere", "bytes", 1e-9) is None


# -- idle_under_span, align_residual --------------------------------------------

@pytest.mark.parametrize("offset", [0.0, 3.0, -UNIX + 0.25])
@pytest.mark.parametrize("launch,notice", [(0.005, 0.005), (0.002, 0.008)])
def test_idle_under_a_span_whatever_lies_between_the_clocks(offset, launch, notice):
    """The device trace counts from its session's start and the program
    from its own: the answer may not depend on what lies between them, nor
    on how the kernel span's slack splits into launch and notice."""
    ctx = traced_run(offset, launch=launch, notice=notice)
    read = reader("idle_under_span").read
    classify = [e for e in ctx["ops_events"][0] if e["name"] == "diff.classify"][0]
    # overlap: the kernel ran inside both spans
    assert read(ctx, **metric_args("idle.classify_s")) == pytest.approx(
        classify["dur"] / 1e6 - 0.89, abs=2e-6
    )
    assert read(ctx, **metric_args("idle.kernel_wait_s")) == pytest.approx(
        launch + notice, abs=2e-6
    )
    # no overlap: nothing ran while the features were written
    assert read(ctx, span="serialise.features", **ANCHOR) == pytest.approx(1.0, abs=2e-6)
    assert reader("align_residual").read(
        ctx, **metric_args("trace.align_residual_s")
    ) == pytest.approx(0.0, abs=2e-6)


def test_a_program_outside_any_span_moves_only_the_span_it_ran_under():
    ctx = traced_run(3.0)
    before = reader("idle_under_span").read(ctx, **metric_args("idle.classify_s"))
    # another program, 0.2 s, while the second command wrote its features
    write = [e for e in ctx["ops_events"][1] if e["name"] == "serialise.features"][0]
    ctx["xla"].append(module(UNIX + 3.0 + write["ts"] / 1e6 + 0.3, 0.2, name="jit_other(7)"))
    read = reader("idle_under_span").read
    assert read(ctx, **metric_args("idle.classify_s")) == pytest.approx(before)
    assert read(ctx, span="serialise.features", **ANCHOR) == pytest.approx(
        (3 * 1.0 - 0.2) / 3, abs=2e-6
    )
    # a span on a worker thread is not the command's main thread's
    assert read(ctx, span="odb.read_blobs_ordered", **ANCHOR) is None


def test_residual_is_what_no_single_offset_can_take_away():
    """Two commands whose kernels cannot both lie inside their spans: the
    first starts with its span (no later offset helps it), the second ends
    8 ms after its span at that offset. The best offset splits it: 4 ms."""
    ctx = traced_run(3.0, starts=(10.0, 20.0), launch=0.0, notice=0.01)
    ctx["xla"][1]["dur"] += 0.018  # ends 8 ms after its span's end
    assert reader("align_residual").read(ctx, **ANCHOR) == pytest.approx(0.004, abs=2e-6)
    offset, residual = span_tree.clock_offset(
        ctx["ops_events"], ctx["xla"], "diff.device.kernel", "jit__classify_"
    )
    assert offset == pytest.approx(3.0 + 0.004, abs=2e-6)
    assert residual == pytest.approx(0.004, abs=2e-6)


def test_no_overlay_without_one_program_per_span():
    ctx = traced_run(3.0)
    ctx["xla"].pop()  # the profiler lost a program
    assert reader("align_residual").read(ctx, **ANCHOR) is None
    assert reader("idle_under_span").read(ctx, span="diff.classify", **ANCHOR) is None
    ctx = traced_run(3.0)
    ctx["xla"] = []  # no device trace at all (a CPU rehearsal)
    assert reader("align_residual").read(ctx, **ANCHOR) is None


NEW_METRICS = [
    "classify.pack_s", "classify.transfer_s", "classify.kernel_wait_s",
    "classify.fetch_s", "classify.select_s", "classify.self_s", "cli.self_s",
    "transfer.h2d_gbps", "idle.classify_s", "idle.kernel_wait_s",
    "trace.align_residual_s",
]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_says_nothing_of_a_program_without_its_span(name):
    """The parent commit's traces have neither the spans nor the parents:
    the metric is left out of its line, and nothing raises."""
    spec = metric_spec(name)
    assert reader(spec["reader"]).read(old_program_run(), **spec["args"]) is None


@pytest.mark.parametrize("name", NEW_METRICS + ["sidecar.load_s"])
def test_reader_finds_its_span_in_a_traced_run(name):
    spec = metric_spec(name)
    value = reader(spec["reader"]).read(traced_run(3.0), **spec["args"])
    assert value is not None and value >= 0
