"""The benchmark's arithmetic on small hand-made inputs with known answers:
statistics, span reduction, device-trace reduction, roofline."""

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
import reduce  # noqa: E402

PLANE = "/device:TPU:0"
KERNEL = "jit__classify_mergesort_core"


def module(start, dur, name=KERNEL + "(123)", plane=PLANE):
    return {"plane": plane, "line": reduce.MODULES_LINE, "name": name,
            "start": start, "dur": dur}


def span(name, ts, dur, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
            "tid": tid, "args": args}


@pytest.mark.parametrize("n,want_median,want_p90", [
    (5, 3.0, 5.0),      # ceil(4.5) = 5th of 5
    (10, 5.5, 9.0),     # 9th of 10
    (12, 6.5, 11.0),    # ceil(10.8) = 11th of 12
])
def test_median_and_nearest_rank_p90(n, want_median, want_p90):
    values = [float(v) for v in range(n, 0, -1)]  # unsorted on purpose
    assert reduce.median(values) == want_median
    assert reduce.nearest_rank(values, 90) == want_p90
    assert reduce.nearest_rank(values, 100) == float(n)


def test_nearest_rank_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        reduce.nearest_rank([], 90)


@pytest.mark.parametrize("intervals,want", [
    ([(0.0, 1.0), (2.0, 1.0)], 2.0),                # disjoint: the sum
    ([(0.0, 2.0), (1.0, 2.0)], 3.0),                # overlapping: once
    ([(0.0, 5.0), (1.0, 1.0), (2.0, 1.0)], 5.0),    # nested
    ([(2.0, 1.0), (0.0, 1.0), (0.5, 1.0)], 2.5),    # out of order
    ([], 0.0),
])
def test_union_seconds(intervals, want):
    assert reduce.union_seconds(intervals) == pytest.approx(want)


def test_busy_is_the_union_and_kernel_time_a_prefix_match():
    events = [
        module(0.0, 1.0),
        module(0.5, 1.0, name="jit_other(9)"),     # overlaps the kernel
        module(3.0, 0.5),
        {"plane": PLANE, "line": reduce.OPS_LINE, "name": "%sort.1 = x",
         "start": 0.0, "dur": 9.0},               # not a module: ignored
        {"plane": "/host:CPU", "line": reduce.MODULES_LINE, "name": KERNEL,
         "start": 0.0, "dur": 9.0},               # read_xplane drops hosts
    ]
    device_only = [e for e in events if e["plane"].startswith(reduce.DEVICE_PLANE)]
    assert reduce.device_busy_seconds(device_only) == pytest.approx(2.0)
    assert reduce.module_seconds(device_only, KERNEL) == pytest.approx(1.5)
    assert reduce.module_seconds(device_only, "jit_absent") is None
    assert reduce.device_busy_seconds([]) is None


def test_busy_is_averaged_over_device_planes():
    events = [module(0.0, 1.0), module(0.0, 3.0, plane="/device:TPU:1")]
    assert reduce.device_busy_seconds(events) == pytest.approx(2.0)
    assert reduce.module_seconds(events, KERNEL) == pytest.approx(2.0)


def test_idle_share_against_a_given_window():
    assert reduce.idle_share(0.834, 2.0) == pytest.approx(58.3)
    assert reduce.idle_share(2.0, 2.0) == 0.0
    with pytest.raises(ValueError):
        reduce.idle_share(1.0, 0.0)


def test_roofline_at_the_10m_bucket():
    rows = 10_485_760
    want_bytes = 2 * rows * 29  # int64 key + 5 x uint32 oid in, int8 class out
    assert costs.classify_sort_join_bytes(rows, rows) == want_bytes
    least = costs.least_seconds(
        "classify_sort_join", "TPU v5 lite", rows_old=rows, rows_new=rows
    )
    assert least == pytest.approx(want_bytes / 819e9)
    share = costs.roofline_share(
        "classify_sort_join", "TPU v5 lite", 0.834, rows_old=rows, rows_new=rows
    )
    assert share == pytest.approx(100 * want_bytes / 819e9 / 0.834)
    assert 0.08 < share < 0.1


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks_for("TPU v9 imaginary")
    with open(costs.PEAKS_FILE) as f:
        assert json.load(f)["source"]


def test_span_mean_per_op_from_chrome_trace_events():
    ops = [
        [span("sidecar.load", 0.0, 0.001), span("diff.classify", 0.01, 2.0,
                                                 rows=10, backend="device_jax")],
        [span("diff.classify", 5.0, 1.0, rows=10, backend="device_jax"),
         span("diff.classify", 6.5, 1.0, rows=10, backend="device_jax")],
    ]
    assert reduce.span_mean_per_op(ops, "diff.classify") == pytest.approx(2.0)
    assert reduce.span_mean_per_op(ops, "serialise.features") is None
    assert reduce.span_mean_per_op([], "diff.classify") is None
    assert reduce.span_attrs(ops[0], "diff.classify", "backend") == ["device_jax"]


def test_host_side_seconds_takes_busy_off_the_device_span():
    ops = [[
        span("diff.classify", 0.0, 2.0),
        span("serialise.features", 2.1, 1.0),
        span("serialise.chunk", 2.2, 0.5),              # nested: not counted
        span("odb.read_blobs_ordered", 2.2, 0.9, tid=2),  # another thread
    ]]
    gaps = dict(reduce.host_side_seconds(ops, [3.3], 0.8, "diff.classify"))
    assert gaps["diff.classify:host_side"] == pytest.approx(1.2)
    assert gaps["serialise.features"] == pytest.approx(1.0)
    assert gaps["cli:outside_spans"] == pytest.approx(0.3)
    assert "serialise.chunk" not in gaps and "odb.read_blobs_ordered" not in gaps


def test_top_device_ops_are_short_names_summed():
    line = reduce.OPS_LINE
    events = [
        {"plane": PLANE, "line": line, "start": 0, "dur": 0.3,
         "name": "%sort.16 = (u32[20971520]{0:T(1024)}) sort(u32[20971520] %p)"},
        {"plane": PLANE, "line": line, "start": 1, "dur": 0.3,
         "name": "%sort.16 = (u32[20971520]{0:T(1024)}) sort(u32[20971520] %p)"},
        {"plane": PLANE, "line": line, "start": 2, "dur": 0.1, "name": "%fusion.2 = s32[] fusion()"},
    ]
    assert reduce.top_device_ops(events, limit=1) == [["sort.16", pytest.approx(0.6)]]
    assert reduce.top_device_ops([]) == []
