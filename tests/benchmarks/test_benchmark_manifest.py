"""BENCHMARK.json and every data file of the benchmark load and agree with
each other and with the limits the benchmark's contract sets. Parametrised
over the files, so each later file is a case. The checks of the manifest are
functions of it, so that a manifest grown as a later PR grows it (a metric
appended, a cell listed on a metric) is shown to pass them too."""

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


def cells_reporting(metric, manifest=MANIFEST):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def metric_spec(name):
    return load(os.path.join(BENCH, "metrics", name + ".json"))


# -- the checks, as functions of a manifest ------------------------------------------

def check_keys(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert manifest["command"][-1].startswith(manifest["paths"][0] + "/")
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert len(json.dumps(manifest, indent=2)) <= 64 * 1024


def check_bounds(manifest):
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert end_to_end["setup_s"]["bound"] == 0.25
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}


def check_names_unique(manifest):
    for group in ("configs", "workloads"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def check_chips(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    assert len(four) <= max(1, len(manifest["workloads"]) // 2)


def check_cell(manifest, cell):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in configs
    config = load(os.path.join(ROOT, configs[cell["config"]]["file"]))
    assert 1 <= len(config["source"]) <= 200
    assert str(cell["chips"]) in config["expect_backend"]
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    reports = [
        m for m in manifest["end_to_end"]
        if cell["name"] in cells_reporting(m, manifest)
    ]
    assert {"setup_s"} < {m["name"] for m in reports}
    assert any(
        cell["name"] in cells_reporting(m, manifest) for m in manifest["per_layer"]
    )


def check_metric(manifest, metric, spec):
    """One metric entry of ``manifest`` and ``spec``, its file's contents."""
    cells = {w["name"] for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = metric in manifest["per_layer"]
    allowed = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"}
    )
    assert allowed <= set(metric) <= allowed | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in {"lower", "higher"}
    assert metric["source"] in SOURCES
    listed = cells_reporting(metric, manifest)
    assert listed and set(listed) <= cells and len(listed) == len(set(listed))
    if per_layer:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        moved = end_to_end[metric["moves"]]
        # every cell that reports this metric also reports the one it moves
        assert set(listed) <= set(cells_reporting(moved, manifest))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    assert spec["what"]
    assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert isinstance(spec["args"], dict)


def check_manifest(manifest, specs):
    """Every check of this file on ``manifest``; ``specs``: each metric's
    file contents by name."""
    check_keys(manifest)
    check_bounds(manifest)
    check_names_unique(manifest)
    check_chips(manifest)
    for cell in manifest["workloads"]:
        check_cell(manifest, cell)
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        check_metric(manifest, metric, specs[metric["name"]])


# -- the manifest as committed -------------------------------------------------------

def test_manifest_has_exactly_the_contracts_keys():
    check_keys(MANIFEST)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_setup_s_is_an_end_to_end_metric_with_the_widest_bound():
    check_bounds(MANIFEST)


def test_names_are_unique():
    check_names_unique(MANIFEST)


def test_at_most_half_the_cells_ask_for_four_chips():
    check_chips(MANIFEST)


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
    check_cell(MANIFEST, cell)


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"], ids=lambda m: m["name"]
)
def test_metric_entry(metric):
    check_metric(MANIFEST, metric, metric_spec(metric["name"]))


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


# -- the guard: what a later PR adds passes every check -------------------------------

#: a metric as the next configuration's PR would append it, and its file
APPENDED = {
    "name": "merge.next_config_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "merge", "moves": "diff_wall_s",
    "workloads": ["merge4m.conflicts1m"],
}
APPENDED_SPEC = {
    "what": "a span of the next configuration's route, mean per traced command",
    "reader": "span_mean_s", "args": {"span": "merge.next_config"},
}


def grown_manifest():
    """An in-memory copy of BENCHMARK.json grown as a later PR grows it: one
    per-layer metric appended, and a cell listed on a metric that did not
    list it (the UUID cell, whose join the join metrics read too)."""
    grown = json.loads(json.dumps(MANIFEST))
    grown["per_layer"].append(dict(APPENDED))
    (entry,) = [m for m in grown["per_layer"] if m["name"] == "join.dense_tile_share"]
    assert "uuid10m.diff_count.churn" not in entry["workloads"]
    entry["workloads"].append("uuid10m.diff_count.churn")
    return grown


def test_an_appended_metric_and_a_listed_cell_pass_every_harness_check():
    import test_benchmark_churn
    import test_benchmark_filtered
    import test_benchmark_uuid_churn

    grown = grown_manifest()
    specs = {
        m["name"]: metric_spec(m["name"])
        for m in grown["end_to_end"] + grown["per_layer"] if m["name"] != APPENDED["name"]
    }
    specs[APPENDED["name"]] = APPENDED_SPEC
    check_manifest(grown, specs)
    # the harness's other tests that read the manifest, on the grown one
    for module in (test_benchmark_churn, test_benchmark_filtered, test_benchmark_uuid_churn):
        module.check_manifest(grown)


#: ``per_layer`` taken by a number: an index or a slice of the list
BY_PLACE = re.compile(r"""\[["']per_layer["']\]\s*\[\s*-?\d*\s*:?\s*-?\d*\s*\]""")


def test_no_harness_test_holds_a_per_layer_entry_by_its_place():
    """Runs of two versions of the manifest are matched entry by entry, by
    place (so a later version appends); a test that held an entry to its
    place would refuse the next appended metric."""
    here = os.path.dirname(os.path.abspath(__file__))
    assert BY_PLACE.search('MANIFEST["per_layer"]' + "[-2:]")
    assert BY_PLACE.search("manifest['per_layer']" + "[0]")
    assert not BY_PLACE.search('MANIFEST["per_layer"] if m["name"] == name]')
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        with open(path) as f:
            found = BY_PLACE.search(f.read())
        assert found is None, f"{os.path.basename(path)}: {found.group(0)}"
