"""Tier-1 tests for the telemetry subsystem (ISSUE 3): span/counter core,
sinks (Chrome trace, Prometheus exposition, phase summary), unified
logging, the importer's span-stack phase accounting, the naming-grammar
guard, the disabled-overhead bound, and the two acceptance flows —
``kart --trace diff`` writing a multi-subsystem Chrome trace, and
``kart stats`` against a running transport server after a fault-injected
(resumed) fetch."""

import io
import json
import logging
import os
import time

import pytest

from kart_tpu import telemetry
from kart_tpu.telemetry import core, sinks


@pytest.fixture(autouse=True)
def clean_registry():
    """Telemetry state is process-global: every test starts and ends
    disabled and empty."""
    telemetry.reset()
    yield
    telemetry.reset()


# -- core -------------------------------------------------------------------


def test_disabled_is_noop():
    with telemetry.span("diff.classify", rows=5):
        pass
    telemetry.incr("odb.objects_read")
    telemetry.gauge_set("runtime.backend_ok", 1)
    telemetry.observe("odb.bytes_inflated", 10)
    snap = telemetry.snapshot()
    assert snap == {"counters": [], "gauges": [], "histograms": []}
    assert telemetry.drain_events() == []


def test_decorator_applied_while_disabled_late_binds():
    """A span decorator applied at import time (telemetry disabled) must
    start recording once telemetry is enabled — enablement is a call-time
    check, not a decoration-time one."""

    @telemetry.span("diff.decorated_early")
    def work():
        return 1

    assert work() == 1  # disabled: plain no-op passthrough
    assert telemetry.all_metric_names() == []
    telemetry.enable(trace=True)
    assert work() == 1
    assert "diff.decorated_early" in telemetry.all_metric_names()
    assert any(e["name"] == "diff.decorated_early" for e in telemetry.drain_events())


def test_counters_gauges_histograms_and_labels():
    telemetry.enable(metrics=True)
    telemetry.incr("transport.retries", verb="fetch-pack")
    telemetry.incr("transport.retries", 2, verb="fetch-pack")
    telemetry.incr("transport.retries", verb="ls-refs")
    telemetry.gauge_set("runtime.backend_ok", 0)
    telemetry.gauge_set("runtime.backend_ok", 1)
    for v in (2.0, 5.0, 3.0):
        telemetry.observe("transport.backoff", v)
    snap = telemetry.snapshot()
    counters = {(n, tuple(sorted(l.items()))): v for n, l, v in snap["counters"]}
    assert counters[("transport.retries", (("verb", "fetch-pack"),))] == 3
    assert counters[("transport.retries", (("verb", "ls-refs"),))] == 1
    assert snap["gauges"] == [("runtime.backend_ok", {}, 1)]
    ((name, _labels, h),) = snap["histograms"]
    assert name == "transport.backoff"
    assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 10.0, 2.0, 5.0)
    # bucketed: cumulative [le, count] pairs ending at +Inf == count, and
    # quantile estimates clamped to the observed range
    assert h["buckets"][-1] == ["+Inf", 3]
    assert sum(1 for _le, c in h["buckets"] if c) >= 1
    assert 2.0 <= h["p50"] <= 5.0
    assert 2.0 <= h["p99"] <= 5.0


def test_span_aggregation_self_vs_cumulative():
    telemetry.enable(spans=True)
    with telemetry.span("diff.outer"):
        time.sleep(0.02)
        with telemetry.span("diff.inner"):
            time.sleep(0.03)
    snap = telemetry.snapshot()
    hists = {n: h for n, _l, h in snap["histograms"]}
    outer, outer_self = hists["diff.outer"], hists["diff.outer.self"]
    inner = hists["diff.inner"]
    # cumulative outer covers the inner phase; self outer excludes it — the
    # two views can't double-book wall-clock
    assert outer["sum"] >= inner["sum"]
    assert outer_self["sum"] == pytest.approx(
        outer["sum"] - inner["sum"], abs=0.01
    )
    assert outer_self["sum"] < outer["sum"]


def test_span_decorator_form():
    telemetry.enable(spans=True)

    @telemetry.span("diff.decorated")
    def work():
        return 42

    assert work() == 42
    names = telemetry.all_metric_names()
    assert "diff.decorated" in names


def test_trace_events_and_chrome_export(tmp_path):
    path = str(tmp_path / "trace.json")
    telemetry.enable(trace=True, trace_path=path)
    with telemetry.span("diff.classify", rows=10):
        pass
    out = sinks.write_chrome_trace()
    assert out == path
    doc = json.load(open(path))
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert spans[0]["name"] == "diff.classify"
    assert spans[0]["cat"] == "diff"
    assert spans[0]["args"] == {"rows": 10}
    assert spans[0]["pid"] == os.getpid()
    assert metas and metas[0]["name"] == "thread_name"
    # the export drained the buffer: a second write has nothing
    assert sinks.write_chrome_trace() is None


def test_prometheus_exposition_format():
    telemetry.enable(metrics=True)
    telemetry.incr("transport.retries", 2, verb='fetch"pack')
    telemetry.gauge_set("runtime.backend_ok", 1)
    telemetry.observe("diff.classify", 0.5)
    text = sinks.prometheus_text()
    assert "# TYPE kart_transport_retries_total counter" in text
    assert 'kart_transport_retries_total{verb="fetch\\"pack"} 2' in text
    assert "kart_runtime_backend_ok 1" in text
    assert "kart_diff_classify_count 1" in text
    assert "kart_diff_classify_sum 0.5" in text


def test_phase_summary_only_lists_spans():
    telemetry.enable(metrics=True)
    with telemetry.span("diff.classify"):
        pass
    telemetry.observe("odb.bytes_inflated", 12345.0)  # not a phase
    text = sinks.phase_summary_text()
    assert "diff.classify" in text
    assert "odb.bytes_inflated" not in text


def test_enable_from_env(monkeypatch, tmp_path):
    monkeypatch.setenv("KART_METRICS", "1")
    monkeypatch.setenv("KART_TRACE", str(tmp_path / "t.json"))
    assert telemetry.enable_from_env()
    assert telemetry.metrics_enabled()
    assert telemetry.tracing_enabled()
    assert telemetry.trace_path() == str(tmp_path / "t.json")


# -- unified logging (satellite: servers/library get real defaults) ---------


def test_configure_logging_idempotent_and_env(monkeypatch):
    logger = logging.getLogger("kart_tpu")
    old = (logger.level, list(logger.handlers), logger.propagate)
    try:
        logger.handlers = []
        telemetry.configure_logging()
        telemetry.configure_logging()  # re-configuring must not stack
        ours = [h for h in logger.handlers if getattr(h, "_kart_tpu_handler", 0)]
        assert len(ours) == 1
        assert logger.level == logging.WARNING
        # propagation stays on: host apps / pytest caplog still see records
        assert logger.propagate is True

        monkeypatch.setenv("KART_LOG", "debug")
        telemetry.configure_logging()  # non-CLI entry points honour KART_LOG
        assert logger.level == logging.DEBUG
        telemetry.configure_logging(verbosity=1)  # explicit -v wins
        assert logger.level == logging.INFO
    finally:
        logger.setLevel(old[0])
        logger.handlers = old[1]
        logger.propagate = old[2]


def test_logging_goes_to_single_kart_logger(monkeypatch):
    logger = logging.getLogger("kart_tpu")
    old_handlers = list(logger.handlers)
    old_level = logger.level
    try:
        logger.handlers = []
        stream = io.StringIO()
        telemetry.configure_logging(verbosity=1, stream=stream)
        logging.getLogger("kart_tpu.transport.retry").info("retrying now")
        text = stream.getvalue()
        assert "kart_tpu.transport.retry" in text
        assert "retrying now" in text
    finally:
        logger.handlers = old_handlers
        logger.setLevel(old_level)


# -- importer phase accounting (satellite: no double-booked wall-clock) -----


def test_phases_nesting_never_double_books():
    p = telemetry.Phases("importer")
    with p.span("encode"):
        time.sleep(0.01)
        with p.span("hash_deflate"):
            time.sleep(0.02)
    p.add("source_read", 0.005)
    total_wall = 0.035 + 0.005
    assert sum(p.self_s.values()) <= total_wall * 1.5  # self never inflates
    # cumulative encode covers the nested hash_deflate; self excludes it
    assert p.cum_s["encode"] >= p.cum_s["hash_deflate"]
    assert p.self_s["encode"] == pytest.approx(
        p.cum_s["encode"] - p.cum_s["hash_deflate"], abs=0.005
    )


def test_import_phase_self_times_sum_to_at_most_total(tmp_path):
    from helpers import make_imported_repo
    from kart_tpu.importer import importer as importer_mod

    make_imported_repo(tmp_path, n=50)
    phases = importer_mod.LAST_IMPORT_PHASES
    assert phases is not None
    assert set(phases) == {
        "source_read",
        "encode",
        "hash_deflate",
        "tree_build",
        "total",
    }
    phase_sum = sum(v for k, v in phases.items() if k != "total")
    # self-times can never sum past wall-clock (the old dict pattern could
    # book one second into two phases when they nested)
    assert phase_sum <= phases["total"] + 1e-6
    assert all(v >= -1e-9 for v in phases.values())


# -- naming grammar (CI satellite a; enforcement now lives in kart lint) ----


def test_all_instrumented_names_match_grammar():
    """The naming-grammar guard is the KTL002 lint rule (ISSUE 4 moved the
    one-off regex scan into kart_tpu/analysis so `kart lint` and this test
    share one source of truth). Here: run exactly that rule over the tree
    and assert it is clean AND that its AST scan still sees the
    instrumentation (an empty scan means the detection rotted, not that
    the tree is clean)."""
    from kart_tpu.analysis.core import FileContext, default_targets, repo_root
    from kart_tpu.analysis.rules import TelemetryGrammar

    rule = TelemetryGrammar()
    bad = []
    for path in default_targets(repo_root()):
        with open(path) as f:
            ctx = FileContext(
                path, os.path.relpath(path, repo_root()), f.read()
            )
        for finding in rule.visit_file(ctx):
            # honor noqa suppressions exactly as `kart lint` does — this
            # test and the CLI must never disagree about the same line
            entry = ctx.noqa.get(finding.line)
            if entry is not None and finding.rule in entry[0]:
                continue
            bad.append(finding)
    # the scan still sees the instrumentation: an empty scan means the
    # detection rotted, not that the tree is clean
    assert rule.names_seen, "no instrumented names found — the scan rotted"
    assert len({n for n, _rel, _line in rule.names_seen}) > 20
    assert not bad, [repr(f) for f in bad]


# -- overhead bound (CI satellite b) ----------------------------------------


def test_disabled_overhead_under_2pct_on_1m_diff():
    """The no-op cost of the disabled instrumentation on a 1M-row columnar
    diff stays under 2% of the diff itself. Computed as
    (calls issued x measured per-call no-op cost) / diff wall-clock —
    differencing two timed runs would drown the ~100ns-scale cost in noise
    and flake; this bound is exact and stable."""
    import numpy as np

    from kart_tpu.diff.engine import get_feature_diff_columnar
    from kart_tpu.parallel.sharded_diff import synthetic_block

    rows = 1_000_000
    old = synthetic_block(rows, seed=0)
    new = synthetic_block(rows, seed=0)
    new.oids = new.oids.copy()
    new.oids[7::1000, 0] ^= 1

    class _Ds:
        path_encoder = None
        repo = None

        @staticmethod
        def get_feature_promise_from_oid(pks, oid):
            return None

    ds = _Ds()

    def workload():
        return get_feature_diff_columnar(ds, ds, blocks=(old, new))

    workload()  # warm
    t0 = time.perf_counter()
    workload()
    work_s = time.perf_counter() - t0

    calls = [0]
    real_span, real_incr = telemetry.span, telemetry.incr
    telemetry.span = lambda *a, **k: (calls.__setitem__(0, calls[0] + 1), real_span(*a, **k))[1]
    telemetry.incr = lambda *a, **k: (calls.__setitem__(0, calls[0] + 1), real_incr(*a, **k))[1]
    try:
        workload()
    finally:
        telemetry.span, telemetry.incr = real_span, real_incr

    n_iter = 100_000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        with real_span("bench.noop"):
            pass
    span_cost = (time.perf_counter() - t0) / n_iter
    t0 = time.perf_counter()
    for _ in range(n_iter):
        real_incr("bench.noop")
    incr_cost = (time.perf_counter() - t0) / n_iter

    overhead_pct = calls[0] * max(span_cost, incr_cost) / work_s * 100.0
    assert overhead_pct < 2.0, (
        f"disabled telemetry costs {overhead_pct:.3f}% of a {rows}-row diff "
        f"({calls[0]} calls x {max(span_cost, incr_cost) * 1e9:.0f}ns)"
    )


# -- acceptance: kart --trace diff ------------------------------------------


def test_trace_diff_covers_four_subsystems(tmp_path, cli_runner, monkeypatch):
    """``kart --trace diff`` on a synth repo writes a valid Chrome trace
    containing spans from >= 4 subsystems (diff engine, odb/packs, sidecar,
    serialise) — the ISSUE 3 acceptance flow."""
    from kart_tpu.cli import cli
    from kart_tpu.synth import synth_repo

    synth_repo(str(tmp_path / "repo"), 12000, edit_frac=0.01, blobs="real")
    trace_path = str(tmp_path / "trace.json")
    monkeypatch.setenv("KART_TRACE", trace_path)
    out_path = str(tmp_path / "out.jsonl")
    r = cli_runner.invoke(
        cli,
        [
            "-C", str(tmp_path / "repo"), "diff", "HEAD^...HEAD",
            "-o", "json-lines", "--output", out_path,
        ],
    )
    assert r.exit_code == 0, r.output
    doc = json.load(open(trace_path))  # valid Chrome trace JSON
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    cats = {e["cat"] for e in spans}
    assert {"diff", "sidecar", "serialise"} <= cats
    assert cats & {"odb", "packs"}
    assert len(cats) >= 4
    for e in spans:
        assert telemetry.NAME_RE.match(e["name"]), e["name"]
        assert e["name"].split(".", 1)[0] in telemetry.SUBSYSTEMS
        assert e["dur"] >= 0
    # and the diff output itself is intact
    with open(out_path) as f:
        assert sum(1 for _ in f) > 1


# -- the one-chip classify path opened up (ISSUE 26) ------------------------


DEVICE_STAGES = [
    "diff.device.pack", "diff.device.transfer", "diff.device.kernel",
    "diff.device.fetch",
]


def _block(n, seed, padded):
    """A sorted FeatureBlock of ``n`` rows, padded to its bucket or not (an
    mmap-backed sidecar block comes unpadded)."""
    from kart_tpu.parallel.sharded_diff import synthetic_block

    block = synthetic_block(n, seed=seed)
    if not padded:
        block.keys, block.oids = block.keys[:n].copy(), block.oids[:n].copy()
    return block


class _StubDataset:
    path_encoder = None
    repo = None

    @staticmethod
    def get_feature_promise_from_oid(pks, oid):
        return None


def _device_classify(rows, padded):
    """One columnar diff of two ``rows``-row blocks, 1 in 100 edited."""
    from kart_tpu.diff.engine import get_feature_diff_columnar

    old, new = _block(rows, 3, padded), _block(rows, 3, padded)
    new.oids = new.oids.copy()
    new.oids[7:rows:100, 0] ^= 1
    ds = _StubDataset()
    return get_feature_diff_columnar(ds, ds, blocks=(old, new)), old, new


@pytest.mark.parametrize("rows,padded", [(900, False), (5000, False), (5000, True)])
def test_device_classify_names_its_four_stages(rows, padded, monkeypatch):
    """The monolithic device classify, forced on XLA-CPU, emits pack ->
    transfer -> kernel -> fetch under ``diff.classify`` and nothing else
    from the device family: each names ``diff.classify`` as its parent,
    counts the bytes of the arrays it handled, and together they take no
    longer than the span around them."""
    from kart_tpu.ops.blocks import bucket_body, bucket_size

    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    telemetry.enable(trace=True)
    diff, old, new = _device_classify(rows, padded)
    assert len(diff) == len(range(7, rows, 100))
    events = telemetry.drain_events()
    (classify,) = [e for e in events if e["name"] == "diff.classify"]
    assert classify["args"]["backend"] == "device_jax"
    assert classify["args"]["counts_only"] is False
    children = [e for e in events if e["args"].get("parent") == "diff.classify"]
    # span events are on, so the call is bracketed by its two clock pings
    assert [e["name"] for e in children] == (
        ["diff.device.clock"] + DEVICE_STAGES
        + ["diff.device.clock", "diff.changed_indices"]
    )
    assert [e["name"] for e in events if e["name"].startswith("diff.device.")] == (
        ["diff.device.clock"] + DEVICE_STAGES + ["diff.device.clock"]
    )
    assert [children[i]["args"]["at"] for i in (0, 5)] == ["start", "end"]
    children = [e for e in children if e["name"] != "diff.device.clock"]
    pack, transfer, kernel, fetch, select = children
    bucket = bucket_size(rows)
    side = bucket * 8 + bucket * 5 * 4  # int64 keys + (n, 5) uint32 oids
    assert pack["args"]["rows"] == 2 * rows and pack["args"]["bucket"] == bucket
    # the host copies one tail per column — a step of the bucket grid, or
    # the whole minimum bucket — and nothing of a block that comes padded
    tail = bucket - bucket_body(bucket)
    assert pack["args"]["bytes"] == (0 if padded else 2 * tail * (8 + 5 * 4))
    assert transfer["args"]["bytes"] == 2 * side
    # one chunk: put and called under its own spans, nothing to hide under
    assert transfer["args"]["ready"] == 0
    assert kernel["args"] == {
        "program": "sort_join", "bucket": bucket, "ready": 0,
        "parent": "diff.classify",
    }
    assert fetch["args"]["bytes"] == 2 * bucket + 3 * 8  # int8 classes + counts
    assert select["args"]["rows"] == 2 * rows
    assert select["args"]["changed"] == 2 * len(diff)
    # in order, one after the other, inside the parent
    for before, after in zip(children, children[1:]):
        assert before["ts"] + before["dur"] <= after["ts"]
    assert classify["ts"] <= pack["ts"]
    assert select["ts"] + select["dur"] <= classify["ts"] + classify["dur"]
    assert sum(e["dur"] for e in children) <= classify["dur"]


def test_device_classify_disabled_records_nothing(monkeypatch):
    """With telemetry off the same path leaves no event, no aggregate and
    no attribute behind: every new span is an early-out at ``__enter__``."""
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    made = []
    real_exit = core._Span.__exit__

    def counting_exit(self, *exc):
        made.append((self.name, self._t0))
        return real_exit(self, *exc)

    monkeypatch.setattr(core._Span, "__exit__", counting_exit)
    diff, _, _ = _device_classify(900, False)
    assert len(diff) == 9
    assert {name for name, _ in made} >= set(DEVICE_STAGES)
    assert all(t0 is None for _, t0 in made)  # none was ever started
    assert telemetry.drain_events() == []
    assert telemetry.snapshot() == {"counters": [], "gauges": [], "histograms": []}


# -- the mesh classify path opened up (ISSUE 28) ----------------------------


MESH_ROUND_STAGES = [
    "diff.device.pack", "diff.device.transfer", "diff.device.kernel",
    "diff.device.fetch",
]


def _count_command(cli_runner, repo, trace_path=None, **env):
    """`kart diff HEAD^...HEAD -o feature-count` -> (stdout, its span events)."""
    from kart_tpu.cli import cli

    if trace_path is not None:
        env["KART_TRACE"] = trace_path
    r = cli_runner.invoke(
        cli, ["-C", repo, "diff", "HEAD^...HEAD", "-o", "feature-count"], env=env
    )
    assert r.exit_code == 0, r.output
    if trace_path is None:
        return r.output, []
    with open(trace_path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    os.remove(trace_path)
    return r.output, events


def test_mesh_count_command_names_every_stage(tmp_path, cli_runner, monkeypatch):
    """`kart diff -o feature-count` forced onto the mesh (all eight virtual
    devices, 256 rows a shard, so several rounds): ``diff.classify`` says
    ``sharded_jax``; under it one ``diff.device.classify`` whose children
    are the splits and, once per round, pack -> transfer -> kernel with the
    fetch of the round before — each with its attributes: a pack's bytes
    are what the host copied (nothing for a round of views), the transfers'
    bytes are what was put and add up to the root's — and the answer is the
    host engine's."""
    import jax

    from kart_tpu.diff import device_batch
    from kart_tpu.synth import synth_repo

    rows, batch_rows = 12000, 256
    repo = str(tmp_path / "repo")
    _, info = synth_repo(repo, rows, edit_frac=0.01)
    monkeypatch.setattr(device_batch, "DEVICE_BATCH_ROWS", batch_rows)
    trace = str(tmp_path / "trace.json")
    host_out, host_events = _count_command(
        cli_runner, repo, trace, KART_DIFF_BACKEND="host_native"
    )
    (classify,) = [e for e in host_events if e["name"] == "diff.classify"]
    assert classify["args"]["backend"] == "host_native"
    assert not [e for e in host_events if e["name"].startswith("diff.device.")]
    telemetry.reset()

    out, events = _count_command(cli_runner, repo, trace, KART_DIFF_SHARDED="1")
    assert out == host_out and f"{info['n_edits']} features changed" in out
    (classify,) = [e for e in events if e["name"] == "diff.classify"]
    assert classify["args"]["backend"] == "sharded_jax"
    assert classify["args"]["counts_only"] is True
    (root,) = [e for e in events if e["name"] == "diff.device.classify"]
    shards, chunks = jax.device_count(), -(-rows // batch_rows)
    rounds = -(-chunks // shards)
    assert rounds > 2
    # the commit rewrites attributes only: both sides have the one key
    # column, every chunk but the last is full, so every round but the last
    # goes over as views of the sidecar's pages — the host copies nothing
    # for it, and puts its count vector (the same for every full round-side)
    # once a command, in the first round
    assert rows % batch_rows and chunks % shards
    counts_bytes = shards * 8
    round_bytes = 2 * (shards * batch_rows * (8 + 5 * 4) + counts_bytes)
    put_bytes = (
        [round_bytes - counts_bytes]
        + [round_bytes - 2 * counts_bytes] * (rounds - 2)
        + [round_bytes]
    )
    assert root["args"] == {
        "rows": rows, "shards": shards, "rounds": rounds, "chunks": chunks,
        "batch_rows": batch_rows, "counts_only": True, "kernel": "sort",
        "bytes": sum(put_bytes), "view_rounds": rounds - 1,
        "parent": "diff.classify",
        "request_id": root["args"]["request_id"],
        "trace_id": classify["args"]["trace_id"],
    }
    device = [e for e in events if e["name"].startswith("diff.device.")]
    children = [e for e in device if e is not root]
    assert all(e["args"]["parent"] == "diff.device.classify" for e in children)
    (splits,) = [e for e in children if e["name"] == "diff.device.splits"]
    assert splits["args"]["chunks"] == chunks
    by_name = {
        name: [e for e in children if e["name"] == name] for name in MESH_ROUND_STAGES
    }
    assert len(children) == 1 + 4 * rounds
    for name, spans in by_name.items():
        assert [e["args"]["round"] for e in spans] == list(range(rounds)), name
    # pack: what the host copied; transfer: what was put — the transfers,
    # not the packs, add up to the root's
    assert [
        (e["args"]["view_sides"], e["args"]["bytes"])
        for e in by_name["diff.device.pack"]
    ] == [(2, 0)] * (rounds - 1) + [(0, round_bytes)]
    assert [e["args"]["bytes"] for e in by_name["diff.device.transfer"]] == put_bytes
    assert sum(e["args"]["bytes"] for e in by_name["diff.device.transfer"]) == (
        root["args"]["bytes"]
    )
    assert {e["args"]["program"] for e in by_name["diff.device.kernel"]} == {
        "mesh_classify"
    }
    # counts only: three int64 come home a round, the classes never do
    assert [e["args"]["bytes"] for e in by_name["diff.device.fetch"]] == [24] * rounds
    # a round's fetch waits until the next round has been sent off
    ends = {
        name: [e["ts"] + e["dur"] for e in spans] for name, spans in by_name.items()
    }
    for r in range(rounds - 1):
        assert ends["diff.device.kernel"][r + 1] <= by_name["diff.device.fetch"][r]["ts"]
    assert sum(e["dur"] for e in children) <= root["dur"] <= classify["dur"]


def test_mesh_classify_counts_rounds_and_bytes():
    """The counters beside ``diff.device.batches``, and the gauges, as the
    benchmark's reference reads them."""
    from kart_tpu.diff.device_batch import classify_blocks_batched
    from kart_tpu.parallel.mesh import make_mesh

    telemetry.enable(metrics=True)
    old, new = _block(3000, 3, False), _block(3000, 3, False)
    classify_blocks_batched(old, new, mesh=make_mesh(4), batch_rows=256,
                            counts_only=True)
    snap = telemetry.snapshot()
    counters = {name: v for name, _, v in snap["counters"]}
    gauges = {name: v for name, _, v in snap["gauges"]}
    rounds = -(--(-3000 // 256) // 4)
    assert gauges["diff.device.shards"] == 4
    assert gauges["diff.device.batch_rows"] == 256
    assert counters["diff.device.rounds"] == rounds == 3
    assert counters["diff.device.batches"] == rounds * 4
    # two rounds of views and a ragged third; the full rounds' count vector
    # is put once, so three of the four view round-sides put 32 bytes fewer
    assert counters["diff.device.view_rounds"] == 2
    assert counters["diff.device.h2d_bytes"] == (
        rounds * 2 * (4 * 256 * 28 + 4 * 8) - 3 * 4 * 8
    )


def test_mesh_classify_disabled_records_nothing(monkeypatch):
    """With telemetry off the mesh path's spans are never started and leave
    nothing behind."""
    from kart_tpu.diff.device_batch import classify_blocks_batched
    from kart_tpu.parallel.mesh import make_mesh

    made = []
    real_exit = core._Span.__exit__

    def counting_exit(self, *exc):
        made.append((self.name, self._t0))
        return real_exit(self, *exc)

    monkeypatch.setattr(core._Span, "__exit__", counting_exit)
    old, new = _block(3000, 3, False), _block(3000, 3, False)
    classify_blocks_batched(old, new, mesh=make_mesh(4), batch_rows=256)
    assert {name for name, _ in made} == set(MESH_ROUND_STAGES) | {
        "diff.device.classify", "diff.device.splits"
    }
    assert all(t0 is None for _, t0 in made)
    assert telemetry.drain_events() == []
    assert telemetry.snapshot() == {"counters": [], "gauges": [], "histograms": []}



def test_cli_command_is_the_root_of_a_traced_diff(tmp_path, cli_runner, monkeypatch):
    """``kart --trace diff``: ``cli.command`` is the root, every other
    main-thread event descends from it through ``args.parent`` and lies
    inside it, every event carries the command's one ``trace_id``, and only
    the root and a worker thread's first span name no parent."""
    from kart_tpu.cli import cli
    from kart_tpu.synth import synth_repo

    synth_repo(str(tmp_path / "repo"), 3000, edit_frac=0.01, blobs="real")
    trace_path = str(tmp_path / "trace.json")
    monkeypatch.setenv("KART_TRACE", trace_path)
    r = cli_runner.invoke(
        cli,
        ["-C", str(tmp_path / "repo"), "diff", "HEAD^...HEAD", "-o", "json-lines",
         "--output", str(tmp_path / "out.jsonl")],
    )
    assert r.exit_code == 0, r.output
    doc = json.load(open(trace_path))
    assert [e for e in doc["traceEvents"] if e["name"] == "kart_trace_epoch"]
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    (root,) = [e for e in spans if e["name"] == "cli.command"]
    assert root["args"]["cmd"] == "diff" and "parent" not in root["args"]
    assert {e["args"]["trace_id"] for e in spans} == {root["args"]["trace_id"]}
    main = [e for e in spans if e["tid"] == root["tid"] and e is not root]
    assert {"diff.classify", "serialise.features", "sidecar.load"} <= {
        e["name"] for e in main
    }
    by_name = {e["name"]: e for e in main}
    for e in main:
        assert root["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"]
        name, hops = e["args"]["parent"], 0
        while name != "cli.command":  # walk up to the root
            name, hops = by_name[name]["args"]["parent"], hops + 1
            assert hops < 16
    others = [e for e in spans if e["tid"] != root["tid"]]
    assert others, "the prefetch thread has a lane of its own"
    for tid in {e["tid"] for e in others}:
        lane = sorted((e for e in others if e["tid"] == tid), key=lambda e: e["ts"])
        orphans = [e for e in lane if "parent" not in e["args"]]
        # only a thread's outermost spans name no parent
        for e in orphans:
            assert not any(
                o is not e and o["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= o["ts"] + o["dur"] for o in lane
            )


# -- acceptance: kart stats vs a fault-injected fetch -----------------------


def _metric(text, name, **labels):
    pat = name
    if labels:
        inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
        pat += "{" + inner + "}"
    for line in text.splitlines():
        if line.startswith(pat + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_stats_reports_fault_injected_fetch_resume(tmp_path, cli_runner, monkeypatch):
    """A fetch torn by KART_FAULTS mid-packstream retries and resumes; the
    server's ``/api/v1/stats`` (via ``kart stats <url>``) reports matching
    retry/resume counters — the ISSUE 3 acceptance flow."""
    import threading

    from kart_tpu.cli import cli
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.synth import synth_repo
    from kart_tpu.transport.http import HttpRemote, make_server
    from kart_tpu.transport.retry import RetryPolicy

    repo, _ = synth_repo(str(tmp_path / "src"), 4000, blobs="real", edit_frac=0.0)
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}/"
        dst = KartRepo.init_repository(str(tmp_path / "dst"))
        client = HttpRemote(url, retry=RetryPolicy(attempts=3, base_delay=0.01))
        wants = list(client.ls_refs()["heads"].values())
        monkeypatch.setenv("KART_FAULTS", "transport.read.frame:1000")
        try:
            client.fetch_pack(dst, wants)
        finally:
            monkeypatch.delenv("KART_FAULTS", raising=False)

        r = cli_runner.invoke(cli, ["stats", url])
        assert r.exit_code == 0, r.output
        text = r.output
        # the torn first attempt retried once...
        assert _metric(text, "kart_transport_retries_total", verb="fetch-pack") == 1
        assert _metric(text, "kart_transport_salvage_events_total") == 1
        # ...and the server saw exactly one resumed fetch-pack (two requests,
        # the second a byte-range resume of the torn stream)
        assert (
            _metric(text, "kart_transport_server_requests_total", verb="fetch-pack")
            == 2
        )
        assert _metric(text, "kart_transport_server_fetch_resumes_total") == 1
        # salvaged + resumed-remainder account for every object received
        salvaged = _metric(text, "kart_transport_objects_salvaged_total")
        received = _metric(text, "kart_transport_objects_received_total")
        assert salvaged == 999  # the fault fired on frame 1000
        total = sum(1 for _ in dst.odb.iter_oids())
        assert salvaged + received == total
    finally:
        server.shutdown()
        server.server_close()


def test_stats_over_stdio_op(tmp_path):
    """The stdio server answers the ``stats`` op with the exposition (the
    ssh-remote path of ``kart stats``)."""
    from helpers import make_imported_repo
    from kart_tpu.transport.http import read_framed, write_framed
    from kart_tpu.transport.stdio import serve_stdio

    repo, _ = make_imported_repo(tmp_path, n=5)
    req = io.BytesIO()
    write_framed(req, {"op": "stats"}, ())
    req.seek(0)
    out = io.BytesIO()
    serve_stdio(repo, req, out)
    out.seek(0)
    resp, _fp = read_framed(out)
    assert "metrics" in resp
    # the stats request itself is counted, so the exposition is never empty
    assert (
        'kart_transport_server_requests_total{verb="stats"} 1'
        in resp["metrics"]
    )


def test_stats_local_cli(cli_runner):
    from kart_tpu.cli import cli

    telemetry.enable(metrics=True)
    telemetry.incr("diff.datasets_diffed", 3)
    r = cli_runner.invoke(cli, ["stats"])
    assert r.exit_code == 0, r.output
    assert "kart_diff_datasets_diffed_total 3" in r.output
    r = cli_runner.invoke(cli, ["stats", "-o", "json"])
    assert r.exit_code == 0, r.output
    assert json.loads(r.output)["counters"]
