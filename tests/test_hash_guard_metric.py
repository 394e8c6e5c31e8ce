"""The device seconds of the hash-keyed count's guard program, as the
benchmark's reader ``xla_module_s`` would read them with the prefix
``jit__hash_guard``, and the accepted metrics that read the classify's
programs: a guard run is counted by the first and by none of the second, and
a trace with no guard run (a program from before the device guard) reads
nothing."""

import glob
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
GUARD = "jit__hash_guard"
CLASSIFY = "jit__classify_mergesort_core_window_split(3)"


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


def module_event(start, dur, name):
    sys.path.insert(0, BENCH)
    try:
        import reduce
    finally:
        sys.path.remove(BENCH)
    return {"plane": "/device:TPU:0", "line": reduce.MODULES_LINE, "name": name,
            "start": start, "dur": dur}


def traced(names):
    """Two traced commands' device events: each name one 0.4 ms run, and one
    50 ms classify run besides."""
    return {"ops_events": [[], []], "ops_walls": [0.1, 0.1],
            "device_kind": "TPU v5 lite",
            "xla": [module_event(1.0 + i, 0.0004, n) for i, n in enumerate(names)]
            + [module_event(2.0, 0.05, CLASSIFY)]}


@pytest.mark.parametrize("runs, want", [
    (5, 0.001),   # five guard runs over two commands
    (0, None),    # the parent's trace: classify runs alone
])
def test_the_guard_program_is_read_by_its_prefix_alone(runs, want):
    ctx = traced([f"{GUARD}({7 + i})" for i in range(runs)])
    got = reader("xla_module_s").read(ctx, prefix=GUARD)
    assert got == (None if want is None else pytest.approx(want))


def test_no_accepted_module_metric_counts_the_guard():
    """Every metric file that reads programs by prefix leaves the guard's
    runs out of its count."""
    prefixes = []
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as f:
            prefix = json.load(f).get("args", {}).get("prefix")
        if prefix:
            prefixes.append(prefix)
    assert "jit__classify_mergesort_core" in prefixes
    assert not [p for p in prefixes if f"{GUARD}(1)".startswith(p)]
