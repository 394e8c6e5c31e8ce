"""``classify.resident_share`` (ISSUE 39), the first per-layer metric a PR
appended after the harness's tests stopped holding ``per_layer`` by place:
the metric file over its reader on hand-made traced commands with known
answers, its entry's fields and cells, and the rule it was appended under.

**The rule.** A new per-layer metric is a new file under
``benchmarks/metrics/`` and a new entry *appended* to ``per_layer`` in
``BENCHMARK.json``. Entries that are there are never moved and none is put
between them: the driver's check compares the entries by place, so one put
anywhere but the end reads there as an edit of the entry whose place it took
(PR 38 was refused for that). A later PR may also add cells to an entry's
``workloads``. No test of the harness holds an entry to a place or a list to
equality; what the tests hold is by name: every name once, every listed cell
a cell, every metric file listed and every listed metric a file."""

import glob
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

from test_benchmark_mesh_readers import CELL as MESH, read_metric  # noqa: E402
from test_benchmark_span_readers import metric_spec, span  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

NAME = "classify.resident_share"
ONE_CHIP = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1]
SIDE = 280_000_000  # bytes of one 10M-row revision's keys and oids


def share(commands):
    return read_metric(NAME, {"ops_events": commands})


def command(resident, read=2 * SIDE, **attrs):
    """One traced command's events: a classify that read ``read`` bytes of
    pages, ``resident`` of them on the device before it (None: a span from
    before the program set either attribute)."""
    if resident is not None:
        attrs.update(input_bytes=read, resident_bytes=resident)
    return [
        span("diff.classify", 0.02, 0.03, "cli.command", chunks=10, **attrs),
        span("cli.command", 0.0, 0.06),
    ]


# -- the metric file over its reader --------------------------------------------

@pytest.mark.parametrize(
    "commands, expected",
    [
        ([command(2 * SIDE)] * 3, 100.0),  # the cells' window: one diff repeated
        ([command(0)] * 3, 0.0),  # revisions the device has not seen; the bypass
        ([command(SIDE)] * 3, 50.0),  # a chain of pushes: the old side is resident
        ([command(0), command(2 * SIDE), command(2 * SIDE)], 200.0 / 3),
        # bytes, not commands: a small cold diff beside a large warm one
        ([command(0, read=SIDE // 10), command(2 * SIDE)],
         100.0 * 2 * SIDE / (2 * SIDE + SIDE // 10)),
    ],
    ids=["warm", "cold", "one_side", "first_command_cold", "weighted_by_bytes"],
)
def test_the_share_is_resident_bytes_over_bytes_read(commands, expected):
    got = share(commands)
    # a share of 0 is a reading (the filtered cell's), not nothing
    assert got is not None and got == pytest.approx(expected)


@pytest.mark.parametrize(
    "commands",
    [
        [command(None)] * 2,  # the parent of PR 38: a span with neither attribute
        [command(0, read=0)],  # nothing read
        [[span("cli.command", 0.0, 0.01)]],  # a command the host engine answered
        [],
    ],
    ids=["no_attributes", "nothing_read", "no_classify_span", "no_commands"],
)
def test_it_reads_nothing_where_there_is_nothing_to_read(commands):
    assert share(commands) is None


def test_a_span_with_one_attribute_alone_does_not_count():
    half = [span("diff.classify", 0.02, 0.03, "cli.command", input_bytes=SIDE),
            span("cli.command", 0.0, 0.06)]
    assert share([half]) is None
    assert share([half, command(SIDE)]) == pytest.approx(50.0)


# -- its entry ---------------------------------------------------------------------

def test_the_entry_is_a_file_over_a_reader_that_was_there_and_lists_the_one_chip_cells():
    spec = metric_spec(NAME)
    assert spec["reader"] == "span_attr_ratio"
    assert spec["args"] == {
        "span": "diff.classify", "numerator": "resident_bytes",
        "denominator": "input_bytes", "scale": 100.0,
    }
    assert len(spec["what"]) > 40
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_span",
        "layer": "classify", "moves": "diff_wall_s",
    }
    # the mesh route never enters the page store: nothing to read there
    assert set(ONE_CHIP) <= set(entry["workloads"]) and MESH not in entry["workloads"]


# -- the rule: append, and hold by name ----------------------------------------------

def test_per_layer_is_held_by_name_every_name_once_every_file_listed():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(names) == len(set(names)), "a per-layer name twice"
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for metric in MANIFEST["per_layer"]:
        listed = metric.get("workloads")
        assert listed is None or (listed and set(listed) <= cells), metric["name"]
        assert listed is None or len(listed) == len(set(listed)), metric["name"]
        assert os.path.exists(os.path.join(BENCH, "metrics", metric["name"] + ".json"))
    files = {
        os.path.basename(p)[: -len(".json")]
        for p in glob.glob(os.path.join(BENCH, "metrics", "*.json"))
    }
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    assert files == set(names) | end_to_end, "a metric file nothing lists, or the reverse"


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_keeps_a_per_layer_metric_for_set_up_and_one_for_what_it_measures(cell):
    """Whatever later PRs append or widen: in every cell some per-layer
    metric moves ``setup_s`` and some moves another end-to-end metric that
    the cell reports (whichever that is: a cell whose command is not a diff
    brings its own)."""
    reports = {
        m["name"] for m in MANIFEST["end_to_end"] if cell in m.get("workloads", [cell])
    }
    moved = {
        m["moves"] for m in MANIFEST["per_layer"] if cell in m.get("workloads", [cell])
    }
    assert "setup_s" in moved and (moved & reports) - {"setup_s"}
