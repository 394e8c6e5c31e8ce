"""BENCHMARK.json and every data file of the benchmark load and agree with
each other and with the limits the benchmark's contract sets. Parametrised
over the files, so each later file is a case."""

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFEST = load(os.path.join(ROOT, "BENCHMARK.json"))
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}


def data_files(kind):
    return sorted(glob.glob(os.path.join(BENCH, kind, "*.json")))


def stem(path):
    return os.path.basename(path)[: -len(".json")]


def cells_reporting(metric):
    return metric.get("workloads", list(CELLS))


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert MANIFEST["command"][-1].startswith(MANIFEST["paths"][0] + "/")
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_setup_s_is_an_end_to_end_metric_with_the_widest_bound():
    assert END_TO_END["setup_s"]["bound"] == 0.25
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_half_the_cells_ask_for_four_chips():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    config = load(os.path.join(ROOT, entry["file"]))
    # the file is the configuration as it is run: same source, same cuts
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    assert config["guarantees"], "a deployment states its guarantees"
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in CONFIGS
    config = load(os.path.join(ROOT, CONFIGS[cell["config"]]["file"]))
    assert 1 <= len(config["source"]) <= 200
    assert str(cell["chips"]) in config["expect_backend"]
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    reports = [
        m for m in MANIFEST["end_to_end"] if cell["name"] in cells_reporting(m)
    ]
    assert {"setup_s"} < {m["name"] for m in reports}
    assert any(cell["name"] in cells_reporting(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"], ids=lambda m: m["name"]
)
def test_metric_entry(metric):
    per_layer = metric in MANIFEST["per_layer"]
    allowed = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"}
    )
    assert allowed <= set(metric) <= allowed | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in {"lower", "higher"}
    assert metric["source"] in SOURCES
    for cell in cells_reporting(metric):
        assert cell in CELLS
    if per_layer:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        moved = END_TO_END[metric["moves"]]
        # every cell that reports this metric also reports the one it moves
        assert set(cells_reporting(metric)) <= set(cells_reporting(moved))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    spec = load(os.path.join(BENCH, "metrics", metric["name"] + ".json"))
    assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert isinstance(spec["args"], dict)


@pytest.mark.parametrize("path", data_files("configs"), ids=stem)
def test_config_file(path):
    config = load(path)
    assert stem(path) in CONFIGS, "a configuration file no cell can reach"
    layer = config["layer"]
    assert os.path.exists(os.path.join(BENCH, "layers", layer["builder"] + ".py"))
    assert layer["params"]["rows"] > 0
    assert set(config["reduced"]) == set(config.get("reduced_why", {}))
    assert config["assumed"]


@pytest.mark.parametrize("path", data_files("traffic"), ids=stem)
def test_traffic_file(path):
    traffic = load(path)
    assert NAME.match(stem(path))
    assert stem(path) in {w["traffic"] for w in MANIFEST["workloads"]}
    assert os.path.exists(os.path.join(BENCH, "ops", traffic["op"] + ".py"))
    assert os.path.exists(
        os.path.join(BENCH, "references", traffic["reference"] + ".py")
    )
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["min_ops"] >= 5
    assert traffic["who"] and traffic["what"]


@pytest.mark.parametrize("path", data_files("metrics"), ids=stem)
def test_metric_file(path):
    names = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    assert stem(path) in names, "a metric file BENCHMARK.json does not list"
    assert load(path)["what"]


def test_every_file_under_paths_is_named_from_a_names_characters():
    for path in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name
