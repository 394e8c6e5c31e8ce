"""The hit share of the device's resident pages (ISSUE 38), as a benchmark
would read it: the ``diff.classify`` span's ``resident_bytes`` over its
``input_bytes`` through the harness's reader ``span_attr_ratio``
(``SPEC`` below is the benchmark's own metric file,
``benchmarks/metrics/classify.resident_share.json``, listed since PR 39).
On hand-made traced commands with known answers, and on the spans the program itself emits (the device route forced onto XLA-CPU at a
small chunk size): 0 on a cold call, 100 on the warm call after it, between
the two where one side alone is resident, 0 for blocks that name no feature
tree, nothing on the spans of a program from before the attributes."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

with open(os.path.join(BENCH, "metrics", "classify.resident_share.json")) as _f:
    SPEC = json.load(_f)  # the metric file itself: the reader and its arguments
SIDE = 280_000_000  # bytes of one 10M-row revision's keys and oids


def reader(name):
    """benchmarks/readers/<name>.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_" + name, os.path.join(BENCH, "readers", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, BENCH)  # the readers import span_tree from there
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


READER = reader(SPEC["reader"])


def span(name, start, dur, parent=None, **args):
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": start * 1e6, "dur": dur * 1e6, "tid": 1, "args": args}


def read_metric(ctx):
    return READER.read(ctx, **SPEC["args"])


def command(resident, read=2 * SIDE, **attrs):
    """One traced command's events: a classify that read ``read`` bytes of
    pages, ``resident`` of them on the device before it."""
    if resident is not None:
        attrs.update(input_bytes=read, resident_bytes=resident)
    return [
        span("diff.classify", 0.02, 0.03, "cli.command", chunks=10, **attrs),
        span("cli.command", 0.0, 0.06),
    ]


@pytest.mark.parametrize(
    "commands, expected",
    [
        ([command(2 * SIDE)] * 3, 100.0),  # the cells' window: one diff repeated
        ([command(0)] * 3, 0.0),  # revisions the device has not seen; the bypass
        ([command(SIDE)] * 3, 50.0),  # a chain of pushes: the old side is resident
        ([command(0), command(2 * SIDE), command(2 * SIDE)], 200.0 / 3),
        # bytes, not commands: a small cold diff beside a large warm one
        ([command(0, read=SIDE // 10), command(2 * SIDE)], 100.0 * 2 * SIDE / (2 * SIDE + SIDE // 10)),
    ],
    ids=["warm", "cold", "one_side", "first_command_cold", "weighted_by_bytes"],
)
def test_the_share_is_resident_bytes_over_bytes_read(commands, expected):
    assert read_metric({"ops_events": commands}) == pytest.approx(expected)


@pytest.mark.parametrize(
    "commands",
    [
        [command(None)] * 2,  # the parent: a span with neither attribute
        [command(0, read=0)],  # nothing read
        [[span("cli.command", 0.0, 0.01)]],  # a command the host engine answered
        [],
    ],
    ids=["no_attributes", "nothing_read", "no_classify_span", "no_commands"],
)
def test_it_reads_nothing_where_there_is_nothing_to_read(commands):
    assert read_metric({"ops_events": commands}) is None


# -- over the program's own spans ---------------------------------------------

_CHUNK = 10_240


@pytest.fixture
def program(monkeypatch):
    from kart_tpu import telemetry as tm
    from kart_tpu.ops import diff_kernel, resident

    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 30))
    tm.reset()
    tm.enable(metrics=True, trace=True)

    def traced(old, new):
        with tm.span("diff.classify"):
            diff_kernel.classify_blocks(old, new)
        return tm.drain_events()

    yield traced
    tm.reset()


def _revision(n, seed, name, every=None):
    from kart_tpu.ops.blocks import FeatureBlock

    rng = np.random.default_rng(7)
    oids = rng.integers(0, 2**32, (n, 5), dtype=np.uint32)
    if every:
        oids[::every, 0] ^= seed
    return FeatureBlock(
        np.arange(n, dtype=np.int64) * 3, oids, None, n,
        tree_oid=name and name * 40,
    )


def test_the_programs_spans_read_0_cold_100_warm_and_half_in_a_chain(program):
    a, b, c = (_revision(35_000, s, n, e) for s, n, e in ((0, "a", None), (1, "b", 9), (2, "c", 7)))
    cold = program(a, b)
    warm = program(a, b)
    chain = program(b, c)  # b was put as the new side of a...b
    assert read_metric({"ops_events": [cold]}) == 0.0
    assert read_metric({"ops_events": [warm]}) == 100.0
    assert read_metric({"ops_events": [chain]}) == pytest.approx(50.0)
    assert read_metric({"ops_events": [cold, warm]}) == pytest.approx(50.0)


def test_blocks_that_name_no_tree_read_0_in_every_command(program):
    old, new = _revision(35_000, 0, None), _revision(35_000, 1, None, 9)
    first, second = program(old, new), program(old, new)
    assert read_metric({"ops_events": [first, second]}) == 0.0
