"""The UUID-keyed republish deployment (``uuid_points_10m_churn``, PR 42) at a
small size on the CPU: the builder's edit sets (counted, not drawn; disjoint;
fresh UUIDs whose hash keys no base row holds), the repository it writes
against the CLI (a msgpack/hash layout, the count the builder's, on the
columnar route), the reference's checks, and the two metrics the cell
brought on hand-made span events and on a parent's trace."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)

from test_benchmark_mesh_readers import read_metric, reference  # noqa: E402
from test_benchmark_span_readers import span  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL = "uuid10m.diff_count.churn"
SEED = 2147483653  # past 32 signed bits, as the driver's are


def builder():
    """benchmarks/layers/uuid_pk_churn_layer.py, loaded as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        "bench_layers_uuid_pk_churn_layer",
        os.path.join(BENCH, "layers", "uuid_pk_churn_layer.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_params(rows):
    with open(os.path.join(BENCH, "configs", "uuid_points_10m_churn.json")) as f:
        params = json.load(f)["layer"]["params"]
    return dict(params, rows=rows)


@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    """A 3,000-row base and the republish of SEED, built once."""
    mod = builder()
    params = config_params(3000)
    base = tmp_path_factory.mktemp("base")
    mod.build_base(str(base), params)
    work = tmp_path_factory.mktemp("work")
    path, info = mod.add_edit_commit(str(base), str(work), params, SEED)
    return mod, params, str(base), path, info


def test_uuids_are_version_4_upper_case_and_seeded():
    mod = builder()
    ids = mod.uuids(np.random.default_rng(1), 500)
    assert ids.dtype == np.dtype("S36")
    import uuid

    for s in ids[:50]:
        u = uuid.UUID(s.decode())
        assert u.version == 4 and str(u).upper() == s.decode()
    assert (mod.uuids(np.random.default_rng(1), 500) == ids).all()


@pytest.mark.parametrize("rows", [3000, 40_000])
def test_edit_sets_are_counted_disjoint_and_the_inserts_keys_are_fresh(rows):
    mod = builder()
    params = config_params(rows)
    base_keys = np.sort(mod._hash_rows(mod.uuids(np.random.default_rng(mod.BASE_SEED), rows)).keys)
    updated, deleted, fresh = mod.edit_sets(params, SEED, base_keys)
    again = mod.edit_sets(params, SEED, base_keys)
    assert all((a == b).all() for a, b in zip((updated, deleted, fresh), again))
    assert (len(updated), len(deleted), len(fresh)) == (rows // 100, rows // 200, rows // 200)
    assert not np.intersect1d(updated, deleted).size
    assert (np.diff(updated) > 0).all() and (np.diff(deleted) > 0).all()
    keys = mod._hash_rows(fresh).keys
    assert len(np.unique(keys)) == len(fresh) and not np.isin(keys, base_keys).any()
    other = mod.edit_sets(params, SEED + 1, base_keys)
    assert not (other[0] == updated).all()


def test_the_republish_is_what_the_cli_counts_on_the_columnar_route(layer, monkeypatch):
    """``kart diff HEAD...churn -o feature-count`` in the builder's repository
    names the builder's count, on the hash-keyed count route (the delta path
    never runs), and the dataset is laid out by msgpack/hash."""
    from click.testing import CliRunner

    from kart_tpu.cli import cli
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff import engine, sidecar

    mod, params, _, path, info = layer
    assert info["path_structure"]["scheme"] == "msgpack/hash"
    churn = info["commits"]["churn"]
    assert churn["n_edits"] == info["n_edits"] == 30 + 15 + 15
    assert churn["rows"] == 3000
    repo = KartRepo(path)
    for rev in ("HEAD", "churn"):
        ds = repo.structure(rev).datasets[mod.DS_PATH]
        assert sidecar.has_sidecar(repo, ds)
        assert sidecar.load_block(repo, ds, pad=False).key_collisions is False

    def no_delta_path(*args, **kwargs):
        raise AssertionError("the count took the delta path")

    monkeypatch.setattr(engine, "get_feature_diff_columnar", no_delta_path)
    result = CliRunner().invoke(
        cli, ["-C", path, "diff", "HEAD...churn", "-o", "feature-count"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0
    checks = reference("feature_count_uuid_churn").check(result.stdout_bytes, info)
    assert checks.pop("hash_guard_ran") in (True, False)  # span aggregates: spans may be off
    assert all(checks.values()), checks


def test_the_derived_sidecar_is_the_one_a_tree_walk_builds(layer, tmp_path):
    """The republish's sidecar was derived (O(changed)); a rebuild from the
    commit's tree gives the same columns."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff import sidecar

    mod, _, _, path, _ = layer
    repo = KartRepo(path)
    ds = repo.structure("churn").datasets[mod.DS_PATH]
    derived = sidecar.load_block(repo, ds, pad=False)
    columns = (np.asarray(derived.keys).copy(), np.asarray(derived.oids).copy(),
               derived.paths.tolist(), np.asarray(derived.envelopes).copy())
    target = sidecar.sidecar_file(repo, ds.feature_tree.oid)
    os.rename(target, str(tmp_path / "derived.kcol"))
    try:
        rebuilt = sidecar.build_sidecar(repo, ds, pad=False)
        assert (np.asarray(rebuilt.keys) == columns[0]).all()
        assert (np.asarray(rebuilt.oids) == columns[1]).all()
        assert rebuilt.paths.tolist() == columns[2]
    finally:
        os.replace(str(tmp_path / "derived.kcol"), target)


def test_reference_wants_each_of_its_checks():
    ref = reference("feature_count_uuid_churn")
    ids = np.array([b"X"] * 3)
    info = {"commits": {"churn": {"inserted_ids": ids[:1], "updated_ids": ids[:1],
                                  "deleted_ids": ids}},
            "path_structure": {"scheme": "msgpack/hash"}}
    good = ref.check(b"layer:\n\t5 features changed\n", info)
    assert good["count_equals_edits"] and good["one_dataset_counted"]
    assert good["path_structure_is_msgpack_hash"]
    assert not ref.check(b"layer:\n\t4 features changed\n", info)["count_equals_edits"]
    info["path_structure"] = {"scheme": "int"}
    assert not ref.check(b"layer:\n\t5 features changed\n", info)["path_structure_is_msgpack_hash"]


def guard_run():
    """ctx of a traced run of two commands, each with one hash guard."""
    return {
        "ops_events": [
            [span("diff.hash_guard", 10.0 + i, 0.004 + 0.002 * i, "cli.command",
                  pairs=100_000, collisions=0),
             span("cli.command", 9.9 + i, 0.2)]
            for i in range(2)
        ],
        "xla": [], "ops_walls": [0.2, 0.2], "device_kind": "TPU v5 lite",
    }


@pytest.mark.parametrize("name,want", [("hash_guard.span_s", 0.005),
                                       ("hash_guard.pairs", 100_000.0)])
def test_new_metrics_on_a_traced_run_and_silent_on_the_parent(name, want):
    assert read_metric(name, guard_run()) == pytest.approx(want)
    parent = {"ops_events": [[span("cli.command", 1.0, 0.2)]], "xla": [],
              "ops_walls": [0.2], "device_kind": "TPU v5 lite"}
    assert read_metric(name, parent) is None


def check_manifest(manifest):
    """The cell and the metrics that read its guard, held by name and by
    membership: a later PR may append metrics anywhere after them and list
    this cell in others' ``workloads``. ``classify.select_s`` reads
    ``diff.changed_indices``, which the hash-keyed count never calls."""
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "uuid_points_10m_churn"
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert {"hash_guard.span_s", "hash_guard.pairs", "classify.resident_share",
            "classify.span_s", "cli.self_s", "sidecar.load_s"} <= listed
    assert "classify.select_s" not in listed
    for name in ("hash_guard.span_s", "hash_guard.pairs"):
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"] and entry["moves"] == "diff_wall_s"


def test_the_cell_and_its_metrics_are_entered_as_the_contract_says():
    check_manifest(MANIFEST)
