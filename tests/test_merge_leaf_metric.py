"""``merge.leaf_s`` (ISSUE 41), as the benchmark reads it: the program span
``merge.leaf_batch`` through the harness's reader ``span_mean_s`` (``SPEC``
below is the benchmark's own metric file, ``benchmarks/metrics/merge.leaf_s.json``):
thread-seconds a command on whichever pool thread made a batch of the merged
tree's leaves. The manifest's entry held by name and by membership, never by
place (PERF.md section 3); hand-made traced commands with known answers; the
spans the program itself emits; nothing on the spans of a program from
before the pool."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "merge.leaf_s"
CELL = "merge4m.conflicts1m"

with open(os.path.join(BENCH, "metrics", NAME + ".json")) as _f:
    SPEC = json.load(_f)  # the metric file itself: the reader and its arguments
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def reader(name):
    """benchmarks/readers/<name>.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_readers_" + name, os.path.join(BENCH, "readers", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, BENCH)  # the readers import reduce from there
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


def read_metric(ctx, spec=SPEC):
    return reader(spec["reader"]).read(ctx, **spec["args"])


def span(name, start, dur, parent=None, tid=1, **args):
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": start * 1e6, "dur": dur * 1e6, "tid": tid, "args": args}


def test_the_entry_is_in_the_manifest_once_and_lists_the_merge_cell():
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert CELL in entry["workloads"]
    assert (entry["layer"], entry["moves"], entry["source"]) == ("merge", "diff_wall_s", "program_span")
    assert (entry["unit"], entry["better"]) == ("s", "lower")
    assert SPEC["args"] == {"span": "merge.leaf_batch"} and len(SPEC["what"]) > 40
    (apply_entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == "merge.apply_s"]
    assert set(entry["workloads"]) <= set(apply_entry["workloads"])  # read against it


def command(t0, batches, workers):
    """One traced merge command: ``batches`` leaf batches of 0.05 s each on
    ``workers`` pool threads under a ``merge.apply`` of their makespan."""
    rounds = -(-batches // workers)
    events = [
        span("merge.leaf_batch", t0 + 0.1 + 0.05 * (i // workers), 0.05, "merge.apply",
             tid=10 + i % workers, rows=262_144, leaves=4_096, bytes=9_437_184)
        for i in range(batches)
    ]
    events += [
        span("merge.apply", t0 + 0.1, 0.05 * rounds + 0.1, "cli.command",
             take_theirs=500_000, leaf_batches=batches, workers=workers),
        span("merge.conflicts", t0 + 0.3 + 0.05 * rounds, 0.24, "cli.command"),
        span("cli.command", t0, 1.0),
    ]
    return events


@pytest.mark.parametrize("workers, parallel", [(1, 16 * 0.05 / 0.9), (4, 16 * 0.05 / 0.3)])
def test_it_is_thread_seconds_a_command_whatever_thread_ran_a_batch(workers, parallel):
    ctx = {"ops_events": [command(10.0, 16, workers), command(20.0, 16, workers)]}
    assert read_metric(ctx) == pytest.approx(16 * 0.05)
    with open(os.path.join(BENCH, "metrics", "merge.apply_s.json")) as f:
        apply_s = read_metric(ctx, json.load(f))
    assert read_metric(ctx) / apply_s == pytest.approx(parallel)


def test_a_command_without_the_batches_counts_in_the_mean():
    ctx = {"ops_events": [command(10.0, 16, 4), [span("cli.command", 20.0, 0.1)]]}
    assert read_metric(ctx) == pytest.approx(16 * 0.05 / 2)


@pytest.mark.parametrize(
    "commands",
    [
        # the parent: the leaves made on the command's thread inside merge.apply
        [[span("merge.apply", 0.1, 0.86, "cli.command", take_theirs=500_000),
          span("cli.command", 0.0, 1.39)]] * 2,
        [[span("cli.command", 0.0, 0.04)]],  # a diff
        [],
    ],
    ids=["no_leaf_spans", "no_merge", "no_commands"],
)
def test_it_reads_nothing_where_there_is_nothing_to_read(commands):
    assert read_metric({"ops_events": commands}) is None


@pytest.mark.parametrize("workers", [1, 4])
def test_the_programs_own_spans_add_up_to_it(workers, tmp_path, monkeypatch):
    from kart_tpu import native
    from kart_tpu import telemetry as tm
    from kart_tpu.core import feature_tree
    from kart_tpu.core.repo import KartRepo

    if native.load_io() is None:
        pytest.skip("no native IO core: no batch is made")
    monkeypatch.setattr(feature_tree, "LEAF_STREAM_ROWS", 1_000)
    monkeypatch.setattr(os, "cpu_count", lambda: workers)
    pks = np.arange(10_000, dtype=np.int64) * 3
    oids = np.random.default_rng(3).integers(0, 256, (len(pks), 20), dtype=np.uint8)
    odb = KartRepo.init_repository(str(tmp_path / "repo")).odb
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        with tm.span("merge.apply"):
            feature_tree.write_int_feature_tree(odb, lambda: iter([(pks, oids)]))
        events = tm.drain_events()
    finally:
        tm.reset()
    batches = [e for e in events if e["name"] == "merge.leaf_batch"]
    assert len(batches) >= 10
    assert read_metric({"ops_events": [events]}) == pytest.approx(
        sum(e["dur"] for e in batches) / 1e6
    )
