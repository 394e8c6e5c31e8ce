"""The merge cell ``merge4m.conflicts1m`` (ISSUE 40): its configuration and
manifest entries, the builder's edit sets and expected answer at 3,000 rows,
the reference accepting the right answer and rejecting wrong ones, each new
metric file over its reader on hand-made spans, the cost function and the
op kind's deadline. All new files; nothing that was there is edited."""

import json
import os
import shutil
import struct
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_mesh_readers import read_metric  # noqa: E402
from test_benchmark_span_readers import metric_spec, module, span  # noqa: E402

import run  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CELL = "merge4m.conflicts1m"
CONFIG = "baseline5_merge_4m_conflicts1m"
KIND = "TPU v5 lite"
WINDOW = "jit__classify_mergesort_core_window_split(1)"
SORT = "jit__classify_mergesort_core_split(2)"
MERGE_METRICS = {
    "merge.load_s": "merge.load_blocks",
    "merge.classify_s": "diff.merge_classify",
    "merge.combine_s": "merge.combine",
    "merge.conflicts_s": "merge.conflicts",
    "merge.apply_s": "merge.apply",
}
NEW_METRICS = [
    *MERGE_METRICS, "merge.conflict_share", "kernel.merge_classify_s",
    "kernel.merge_classify_roofline",
]


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def traffic():
    with open(os.path.join(BENCH, "traffic", "merge_dry_run.json")) as f:
        return json.load(f)


def params(rows=3000):
    return dict(config()["layer"]["params"], rows=rows)


# -- the entries -----------------------------------------------------------------

def test_the_cell_is_one_chip_on_its_own_configuration_with_its_own_traffic():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "merge_dry_run", 1)
    # a later PR may add another cell on this configuration (another traffic)
    assert CELL in [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert "configs[4]" in entry["source"] and entry["reduced"] == ["sidecar_vertex_column"]


def test_the_configuration_states_the_mix_the_issue_gives():
    cfg = config()
    p = cfg["layer"]["params"]
    counts = {k[: -len("_frac")]: int(p["rows"] * v) for k, v in p.items()
              if k.endswith("_frac")}
    assert p["rows"] == 4_000_000 and cfg["layer"]["builder"] == "int_pk_merge_layer"
    assert counts == {
        "edit_edit": 900_000, "edit_delete": 50_000, "add_add": 50_000,
        "same": 125_000, "theirs_edit": 250_000, "theirs_delete": 125_000,
        "theirs_insert": 125_000, "ours_edit": 250_000,
    }
    assert counts["edit_edit"] + counts["edit_delete"] + counts["add_add"] == 1_000_000
    assert cfg["expect_backend"] == {"1": "device_jax"}
    assert set(cfg["assumed"]) >= {"rows", "mix", "edit_rows", "insert_ids"}
    assert len(cfg["guarantees"]) >= 7
    assert any("dry run" in g for g in cfg["guarantees"])


def test_the_traffic_is_the_dry_run_in_a_closed_loop_with_a_deadline():
    t = traffic()
    assert t["argv"] == ["-C", "{repo}", "merge", "theirs", "--dry-run", "-o", "json"]
    assert (t["op"], t["reference"]) == ("cli_merge", "merge_conflicts")
    assert t["backend_span"] == "diff.merge_classify"
    assert t["fallback_counter"] == "diff.device.fallbacks"
    assert t["max_command_s"] == MANIFEST["run_seconds"]
    reference = run.load_module("references", "merge_conflicts")
    assert "names_the_conflict_count" in t["rehearsal_checks"]
    assert len(t["rehearsal_checks"]) == 8
    assert reference.expected_document({"conflicts": 1_000_000}) == {
        "kart.merge/v1": {"conflicts": {"layer": {"feature": 1000000}},
                          "state": "merging", "dryRun": True}
    }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_is_in_the_manifest_once_and_lists_the_merge_cell(name):
    """Held by name and by membership, never by place or by equality (the
    rule of ``test_benchmark_resident_share.py``): a later PR may append
    metrics after these and list further cells on them."""
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "diff_wall_s"
    assert entry["layer"] == ("kernel" if name.startswith("kernel.") else "merge")
    assert len(metric_spec(name)["what"]) > 40


def test_the_cell_reports_the_page_stores_hit_share():
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == "classify.resident_share"]
    assert CELL in entry["workloads"]


# -- the builder -------------------------------------------------------------------

@pytest.fixture(scope="module")
def builder():
    return run.load_module("layers", "int_pk_merge_layer")


def fake_write(pks, ratings):
    """Twenty bytes that name (pk, rating), in place of a blob's oid."""
    out = np.zeros((len(pks), 20), dtype=np.uint8)
    out[:, :8] = np.asarray(pks, dtype="<i8").view(np.uint8).reshape(-1, 8)
    out[:, 8:16] = np.asarray(ratings, dtype="<f8").view(np.uint8).reshape(-1, 8)
    return out


def columns_of(builder, seed=2147483653, rows=3000):
    p = params(rows)
    sets = builder.edit_sets(p, seed)
    pks = builder.PK_BASE + np.arange(rows, dtype=np.int64)
    base_oids = fake_write(pks, pks / 2.0)
    columns = builder.branch_columns(p, sets, base_oids, fake_write)
    return p, sets, base_oids, columns


def test_the_edit_sets_are_disjoint_counted_and_a_function_of_the_seed(builder):
    p = params()
    sets = builder.edit_sets(p, 2147483653)
    drawn = np.concatenate([sets[k] for k in builder.ROW_KINDS])
    assert len(drawn) == len(set(drawn.tolist())) == 675 + 37 + 93 + 187 + 93 + 187
    assert {k: len(v) for k, v in sets.items()} == {
        "edit_edit": 675, "edit_delete": 37, "same": 93, "theirs_edit": 187,
        "theirs_delete": 93, "ours_edit": 187, "add_add": 37, "theirs_insert": 93,
    }
    first = builder.PK_BASE + 3000
    assert sets["add_add"].tolist() == list(range(first, first + 37))
    assert sets["theirs_insert"].tolist() == list(range(first + 37, first + 130))
    again = builder.edit_sets(p, 2147483653)
    other = builder.edit_sets(p, 2147483654)
    assert all(np.array_equal(sets[k], again[k]) for k in sets)
    assert not np.array_equal(sets["edit_edit"], other["edit_edit"])


def test_the_expected_answer_is_the_three_way_rule_per_key(builder):
    """The builder's expected conflicts and merged columns against a dict
    per key over its own branch columns: the rule written out once more."""
    p, sets, base_oids, columns = columns_of(builder)
    pks = builder.PK_BASE + np.arange(3000, dtype=np.int64)
    expected = builder.expected_merge(p, sets, base_oids, columns)

    def as_dict(keys, oids):
        return {int(k): bytes(o) for k, o in zip(keys, oids)}

    a = as_dict(pks, base_oids)
    o = as_dict(*columns["ours"])
    t = as_dict(*columns["theirs"])
    conflicts, merged, taken = [], dict(o), 0
    for k in sorted(set(a) | set(o) | set(t)):
        av, ov, tv = a.get(k), o.get(k), t.get(k)
        if ov == tv or tv == av:
            continue
        if ov == av:
            taken += 1
            if tv is None:
                del merged[k]
            else:
                merged[k] = tv
        else:
            conflicts.append(k)
    assert expected["conflict_pks"].tolist() == conflicts and len(conflicts) == 749
    assert expected["take_theirs"] == taken == 187 + 93 + 93
    for v, side in enumerate((a, o, t)):
        present = expected["conflict_present"][v]
        assert present.tolist() == [k in side for k in conflicts]
        assert [bytes(x) for x in expected["conflict_oids"][v][present]] == [
            side[k] for k in conflicts if k in side
        ]
    merged_pks, merged_oids = expected["merged"]
    assert as_dict(merged_pks, merged_oids) == merged
    assert len(columns["ours"][0]) == 3037 and len(columns["theirs"][0]) == 3000


# -- the reference -----------------------------------------------------------------

def index_bytes(info, merged_tree="a" * 40):
    """A ``MERGE_INDEX`` in the columnar encoding, as the program writes it
    for an int-pk dataset (derived labels and paths), from ``info``."""
    pks = np.asarray(info["conflict_pks"], dtype="<i8")
    header = json.dumps({"mergedTree": merged_tree, "n": len(pks), "resolves": {}}).encode()
    spec = json.dumps({"ds_path": "layer"}).encode()
    labels = struct.pack("<I", len(spec)) + spec + pks.tobytes()
    out = [b"KMIX2\n", struct.pack("<I", len(header)), header,
           struct.pack("<QQ", 0xFFFFFFFFFFFFFFFD, len(labels)), labels]
    for v in range(3):
        present = np.asarray(info["conflict_present"][v], dtype=np.uint8)
        oids = np.asarray(info["conflict_oids"][v], dtype=np.uint8)
        out += [struct.pack("<Q", len(present)), present.tobytes(),
                struct.pack("<Q", oids.size), oids.tobytes()]
        if present.all() and v:
            out.append(struct.pack("<QQ", 0xFFFFFFFFFFFFFFFF, 0))
        else:
            paths = b"\x00".join(b"p" if ok else b"" for ok in present)
            out += [struct.pack("<Q", len(paths)), paths]
    return b"".join(out)


class FakeOdb:
    """A store whose every root tree holds ``feature_tree`` at the dataset's
    feature path."""

    def __init__(self, feature_tree):
        self.feature_tree = feature_tree

    def tree(self, oid):
        return self

    def get_or_none(self, path):
        assert path == "layer/.table-dataset/feature"
        odb = self

        class Node:
            oid = odb.feature_tree

        return Node if self.feature_tree else None


@pytest.fixture(scope="module")
def answer(builder):
    p, sets, base_oids, columns = columns_of(builder)
    info = builder.expected_merge(p, sets, base_oids, columns)
    info["merged_pks"], info["merged_oids"] = info.pop("merged")
    info["conflicts"] = len(info["conflict_pks"])
    return info


@pytest.fixture(scope="module")
def answer_tree(answer):
    """The feature tree of the expected merged columns, as the reference names it."""
    reference = run.load_module("references", "merge_conflicts")
    return reference.feature_tree_oid(answer["merged_pks"], answer["merged_oids"])


def spoiled(info, how):
    info = {k: np.array(v) if isinstance(v, np.ndarray) else v for k, v in info.items()}
    if how == "a_dropped_conflict":
        keep = np.ones(len(info["conflict_pks"]), dtype=bool)
        keep[100] = False
        info["conflict_pks"] = info["conflict_pks"][keep]
        info["conflict_present"] = info["conflict_present"][:, keep]
        info["conflict_oids"] = info["conflict_oids"][:, keep]
    elif how == "a_swapped_oid":
        info["conflict_oids"][1, 5], info["conflict_oids"][2, 5] = (
            info["conflict_oids"][2, 5].copy(), info["conflict_oids"][1, 5].copy()
        )
    elif how == "a_version_that_should_be_absent":
        v, i = np.argwhere(~info["conflict_present"])[0]
        info["conflict_present"][v, i] = True
    elif how == "another_pk":
        info["conflict_pks"][7] += 1
    return info


def test_the_reference_accepts_the_builders_answer(answer, answer_tree):
    reference = run.load_module("references", "merge_conflicts")
    checks = reference.check_index(index_bytes(answer), answer, FakeOdb(answer_tree))
    assert checks == {
        "conflict_pks_are_the_builders": True,
        "versions_are_the_builders": True,
        "merged_tree_is_the_builders": True,
    }


@pytest.mark.parametrize(
    "how, fails",
    [
        ("a_dropped_conflict", {"conflict_pks_are_the_builders", "versions_are_the_builders"}),
        ("another_pk", {"conflict_pks_are_the_builders", "versions_are_the_builders"}),
        ("a_swapped_oid", {"versions_are_the_builders"}),
        ("a_version_that_should_be_absent", {"versions_are_the_builders"}),
    ],
)
def test_the_reference_rejects_a_wrong_conflict_set(how, fails, answer, answer_tree):
    reference = run.load_module("references", "merge_conflicts")
    checks = reference.check_index(
        index_bytes(spoiled(answer, how)), answer, FakeOdb(answer_tree)
    )
    assert {name for name, ok in checks.items() if not ok} == fails


def wrong_tree(reference, answer, how):
    """The oid of a feature tree a wrong apply would have written."""
    pks, oids = answer["merged_pks"], answer["merged_oids"].copy()
    if how == "none":
        return None
    if how == "a_clean_change_of_theirs_left_out":  # one row keeps another's blob
        oids[11] = oids[12]
    elif how == "a_deleted_feature_kept":
        gone = np.setdiff1d(np.arange(pks[0], pks[0] + 3000), pks)[0]
        at = np.searchsorted(pks, gone)
        pks, oids = np.insert(pks, at, gone), np.insert(oids, at, oids[0], axis=0)
    elif how == "an_added_feature_missing":
        pks, oids = pks[:-1], oids[:-1]
    return reference.feature_tree_oid(pks, oids)


@pytest.mark.parametrize("how", [
    "none", "a_clean_change_of_theirs_left_out", "a_deleted_feature_kept",
    "an_added_feature_missing",
])
def test_the_reference_rejects_a_wrong_merged_tree(how, answer):
    reference = run.load_module("references", "merge_conflicts")
    checks = reference.check_index(
        index_bytes(answer), answer, FakeOdb(wrong_tree(reference, answer, how))
    )
    assert {name for name, ok in checks.items() if not ok} == {
        "merged_tree_is_the_builders"
    }


def test_the_references_tree_is_the_one_the_programs_builder_writes(answer, answer_tree, tmp_path):
    """Two implementations with nothing in common — ``hashlib`` over names
    made a row at a time here, the program's plan and leaf stream there —
    name the same tree for the 3,000-row answer."""
    from kart_tpu.core.feature_tree import build_int_feature_tree
    from kart_tpu.core.repo import KartRepo

    odb = KartRepo.init_repository(str(tmp_path / "store")).odb
    assert answer_tree == build_int_feature_tree(
        odb, answer["merged_pks"], answer["merged_oids"]
    )


def git_mktree(cwd, lines):
    return subprocess.run(
        ["git", "mktree", "--missing"], input="".join(lines), cwd=cwd, text=True,
        capture_output=True, check=True,
    ).stdout.strip()


@pytest.mark.skipif(not shutil.which("git"), reason="no git binary")
def test_the_references_tree_is_the_one_git_names(tmp_path):
    """Against git itself: pks of three msgpack widths in three leaves, two
    of them under one directory; the layout written out by hand."""
    reference = run.load_module("references", "merge_conflicts")
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    pks = np.array([5, 70, 200, 70000, 70001, (1 << 24) + 9], dtype=np.int64)
    oids = (np.arange(120, dtype=np.uint8) * 7 + 1).reshape(6, 20)
    alphabet = "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_"
    names = {  # urlsafe_b64(msgpack([pk])), each worked out by hand
        5: "kQU=", 70: "kUY=", 200: "kczI", 70000: "kc4AARFw", 70001: "kc4AARFx",
        (1 << 24) + 9: "kc4BAAAJ",
    }
    tree = {}
    for pk, oid in zip(pks.tolist(), oids):
        number = pk // 64
        digits = [alphabet[(number // 64**k) % 64] for k in (3, 2, 1, 0)]
        node = tree
        for d in digits:
            node = node.setdefault(d, {})
        node[names[pk]] = bytes(oid).hex()

    def write(node):
        lines = []
        for name in sorted(node, key=str.encode):
            child = node[name]
            if isinstance(child, dict):
                lines.append(f"040000 tree {write(child)}\t{name}\n")
            else:
                lines.append(f"100644 blob {child}\t{name}\n")
        return git_mktree(str(tmp_path), lines)

    assert reference.feature_tree_oid(pks, oids) == write(tree)


def test_the_reference_reads_the_json_encoding_small_merges_get(answer):
    reference = run.load_module("references", "merge_conflicts")
    names = ("ancestor", "ours", "theirs")
    conflicts = {}
    for i, pk in reversed(list(enumerate(answer["conflict_pks"].tolist()))):
        conflicts[f"layer:feature:{pk}"] = {
            name: {"path": "p", "oid": bytes(answer["conflict_oids"][v][i]).hex()}
            if answer["conflict_present"][v][i] else None
            for v, name in enumerate(names)
        }
    raw = json.dumps({"kart.merge_index/v1": {
        "mergedTree": "a" * 40, "conflicts": conflicts, "resolves": {}}}).encode()
    merged_tree, pks, present, oids = reference.read_merge_index(raw)
    assert merged_tree == "a" * 40
    np.testing.assert_array_equal(pks, answer["conflict_pks"])
    np.testing.assert_array_equal(present, answer["conflict_present"])
    np.testing.assert_array_equal(oids[present], answer["conflict_oids"][present])


@pytest.mark.parametrize(
    "document, ok",
    [
        ({"kart.merge/v1": {"conflicts": {"layer": {"feature": 749}},
                            "state": "merging", "dryRun": True}}, True),
        ({"kart.merge/v1": {"conflicts": {"layer": {"feature": 748}},
                            "state": "merging", "dryRun": True}}, False),
        ({"kart.merge/v1": {"conflicts": {"layer": {"feature": 749}},
                            "state": "merging"}}, False),
        ({"kart.merge/v1": {"commit": "c" * 40, "merging": False, "dryRun": True}}, False),
    ],
    ids=["right", "wrong_count", "not_a_dry_run", "no_conflicts"],
)
def test_the_reference_holds_the_dry_runs_document_to_the_builders_count(document, ok, answer):
    reference = run.load_module("references", "merge_conflicts")
    assert (document == reference.expected_document(answer)) is ok


# -- the metric files over their readers ---------------------------------------------

CENSUS = dict(rows_ancestor=4_000_000, rows_ours=4_050_000, rows_theirs=4_000_000,
              union=4_175_000, conflicts=1_000_000, take_theirs=500_000)


def merge_command(t0, census=CENSUS):
    """The span events of one traced merge command that starts at ``t0``."""
    root = "cli.command"
    return [
        span("merge.load_blocks", t0 + 0.01, 0.002, root, source="sidecar"),
        span("diff.classify", t0 + 0.02, 0.03, "diff.merge_classify", side="ours"),
        span("diff.classify", t0 + 0.05, 0.04, "diff.merge_classify", side="theirs"),
        span("merge.combine", t0 + 0.09, 0.2, "diff.merge_classify", both=1_125_000),
        span("diff.merge_classify", t0 + 0.02, 0.3, root, backend="device_jax", **census),
        span("merge.apply", t0 + 0.4, 2.5, root, take_theirs=500_000),
        span("merge.conflicts", t0 + 3.0, 0.25, root, conflicts=1_000_000),
        span(root, t0, 3.5),
    ]


def merge_run(census=CENSUS):
    commands = [merge_command(10.0, census), merge_command(20.0, census)]
    xla = []
    for t0 in (10.0, 20.0):
        xla += [module(t0 + 0.03 + 0.005 * i, 0.004, WINDOW) for i in range(8)]
        xla.append(module(t0 + 0.08, 0.008, SORT))
        xla.append(module(t0 + 0.019, 0.0000002, "jit__clock_probe(3)"))
        xla.append(module(t0 + 0.021, 0.001, "jit__resident_page(4)"))
    return {"ops_events": commands, "ops_walls": [3.5, 3.5], "xla": xla,
            "device_kind": KIND, "clock": {}}


@pytest.mark.parametrize("name, seconds", [
    ("merge.load_s", 0.002), ("merge.classify_s", 0.3), ("merge.combine_s", 0.2),
    ("merge.conflicts_s", 0.25), ("merge.apply_s", 2.5),
])
def test_a_merge_stage_is_its_spans_mean_seconds_a_command(name, seconds):
    assert metric_spec(name)["args"] == {"span": MERGE_METRICS[name]}
    assert read_metric(name, merge_run()) == pytest.approx(seconds)


def test_the_conflict_share_is_conflicts_over_the_union():
    assert read_metric("merge.conflict_share", merge_run()) == pytest.approx(
        100.0 * 1_000_000 / 4_175_000
    )


def test_the_kernels_seconds_are_both_joins_and_neither_the_ping_nor_the_page():
    assert read_metric("kernel.merge_classify_s", merge_run()) == pytest.approx(
        8 * 0.004 + 0.008
    )


def test_the_roofline_counts_each_revision_once_and_a_byte_a_union_key():
    costs_merge = __import__("costs_merge")
    need = (4_000_000 + 4_050_000 + 4_000_000) * 28 + 4_175_000
    assert costs_merge.merge_classify_bytes(
        rows_ancestor=4_000_000, rows_ours=4_050_000, rows_theirs=4_000_000,
        union=4_175_000,
    ) == need == 341_575_000
    least = costs_merge.least_seconds(
        "merge_classify", KIND, rows_ancestor=4_000_000, rows_ours=4_050_000,
        rows_theirs=4_000_000, union=4_175_000,
    )
    assert least == pytest.approx(need / 819e9)
    got = read_metric("kernel.merge_classify_roofline", merge_run())
    assert got == pytest.approx(100.0 * 2 * least / (2 * (8 * 0.004 + 0.008)))
    assert 0 < got < 100
    with pytest.raises(KeyError):
        costs_merge.least_seconds("merge_classify", "no such chip", rows_ancestor=1,
                                  rows_ours=1, rows_theirs=1, union=1)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_parent_without_the_spans_or_the_census_reads_nothing(name):
    """The parent's merge has one span, ``diff.merge_classify`` with
    ``rows`` and ``backend`` alone, and on one chip runs another program:
    every new metric but the span's own seconds reads nothing and none
    raises."""
    events = [span("diff.merge_classify", 0.1, 9.0, "cli.command", rows=4_050_000,
                   backend="device_jax"), span("cli.command", 0.0, 120.0)]
    ctx = {"ops_events": [events], "ops_walls": [120.0], "device_kind": KIND,
           "xla": [module(0.2, 8.0, "jit__merge_classify_padded_core(5)")], "clock": {}}
    got = read_metric(name, ctx)
    assert (got == pytest.approx(9.0)) if name == "merge.classify_s" else got is None
    assert read_metric(name, {**ctx, "ops_events": [], "ops_walls": [], "xla": []}) is None


# -- the op kind ---------------------------------------------------------------------

class Clock:
    def __init__(self, *readings):
        self.readings = list(readings)

    def perf_counter(self):
        return self.readings.pop(0)


def make_op(monkeypatch, tmp_path, wall, compile_s):
    module_ = run.load_module("ops", "cli_merge")
    base = module_.Op.__mro__[1]
    calls = []

    def fake_run(self, env=None):
        if len(calls) == 0 and compile_s:
            self._on_duration(module_.COMPILE_EVENT, compile_s)
            self._on_duration("/jax/some/other/event", 100.0)
        calls.append(env)
        return 0, b"{}"

    monkeypatch.setattr(base, "run", fake_run)
    monkeypatch.setattr(module_, "time", Clock(100.0, 100.0 + wall))
    op = module_.Op(traffic(), str(tmp_path / "repo"), str(tmp_path))
    return module_, op, calls


@pytest.mark.parametrize("wall, compile_s", [(4.0, 0.0), (29.9, 0.0), (95.0, 70.0)],
                         ids=["fast", "just_inside", "slow_only_by_its_compiles"])
def test_the_first_command_inside_the_deadline_goes_on(wall, compile_s, monkeypatch, tmp_path):
    module_, op, calls = make_op(monkeypatch, tmp_path, wall, compile_s)
    assert op.run() == (0, b"{}")
    assert op.run(env=op.HOST_TWIN_ENV) == (0, b"{}")  # untimed from here on
    assert calls == [None, op.HOST_TWIN_ENV]
    assert op.HOST_TWIN_ENV["KART_DIFF_BACKEND"] == "host_native"


@pytest.mark.parametrize("wall, compile_s", [(30.5, 0.0), (130.0, 0.0), (130.0, 70.0)],
                         ids=["just_outside", "the_parents_walks", "slow_beyond_its_compiles"])
def test_a_first_command_the_window_cannot_hold_ends_the_run(wall, compile_s, monkeypatch, tmp_path, capsys):
    module_, op, calls = make_op(monkeypatch, tmp_path, wall, compile_s)
    with pytest.raises(SystemExit) as stop:
        op.run()
    assert stop.value.code == module_.TOO_SLOW and module_.TOO_SLOW not in (0, 1)
    assert len(calls) == 1
    assert "max_command_s" in capsys.readouterr().err
