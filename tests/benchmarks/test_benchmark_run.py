"""One tiny CPU rehearsal of benchmarks/run.py per cell, each in a child
process as the driver runs it: the last line parses and holds the contract's
keys, the layer builder's edit set is what `kart diff` names (the reference
is checked against the program here, where it is cheap), and the run ends
``correct: false`` with a non-zero exit code because no TPU answered."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

REFERENCE_CHECKS = {
    "diff_count": {"one_dataset_counted", "count_equals_edits"},
    "diff_jsonl": {"names_the_edited_pks", "values_are_the_builders"},
}


def rehearse(cell, trace, cache_dir, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(cache_dir))
    return subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
         "--seed", "2147483653", "--seconds", "1", "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, timeout=120,
    )


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_the_cpu(cell, trace, tmp_path):
    proc = rehearse(cell["name"], trace, tmp_path, "--rows", "3000",
                    "--cache-dir", str(tmp_path / "cache"))
    assert proc.returncode == 1, proc.stderr.decode()[-2000:]
    result = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    wanted = {"correct", "attempted", "failed", "metrics", "device"}
    assert wanted <= set(result) <= wanted | {"breakdown", "checks"}
    assert result["correct"] is False and result["failed"] == 0
    assert result["attempted"] >= 5
    assert result["device"]["platform"] == "cpu"
    checks = result["checks"]
    assert not checks["platform_is_tpu"] and not checks["not_a_rehearsal"]
    # the reference against the program: the builder's edits are what the
    # command names, and the host twin answers with the same bytes
    for name in REFERENCE_CHECKS[cell["traffic"]] | {"equals_twin", "twin_is_host"}:
        assert checks[name] is True, name
    assert checks["no_compile_in_window"] and checks["no_fallbacks"]
    kind = "per_layer" if trace else "end_to_end"
    listed = {
        m["name"]: m["unit"] for m in MANIFEST[kind]
        if cell["name"] in m.get("workloads", [cell["name"]])
    }
    assert result["metrics"], "a run reports at least one metric"
    for name, metric in result["metrics"].items():
        assert metric["unit"] == listed[name] and metric["value"] >= 0
    if trace:
        assert {"device_ops", "idle_gaps"} == set(result["breakdown"])
        assert result["device"]["window_s"] > 0
    else:
        assert set(result["metrics"]) == set(listed)
    assert not os.listdir(tmp_path) or os.listdir(tmp_path) == ["cache"], (
        "the run left its working directory behind"
    )


def test_no_accelerator_means_no_result(tmp_path):
    """Without the rehearsal option a run that finds no TPU prints no result
    and exits non-zero, before it builds anything."""
    cell = MANIFEST["workloads"][0]["name"]
    proc = rehearse(cell, 0, tmp_path, "--cache-dir", str(tmp_path / "cache"))
    assert proc.returncode not in (0, 1)
    assert proc.stdout.decode().strip() == ""
    assert not os.path.exists(tmp_path / "cache")
