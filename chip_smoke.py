#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that kart-tpu still starts on the chip.

Drives the system's main path once, in ONE process, through the entry
points a user calls (``kart diff``, with and without a spatial filter set, ``kart merge``, the
spatial-filter envelope scan) at the size of ``BASELINE.json`` config 2 — a 10M-row point
layer with a 1% edit commit — under default (auto) routing, and compares
every answer with the host engine's. Then it runs, directly on a one-device
mesh, the device programs auto routing cannot reach on one chip
(docs/DEVICE.md "what runs where"), each against its host twin.

    python chip_smoke.py            # one chip, every phase
    python chip_smoke.py --chips 4  # four chips: the mesh diff + merge only

One JSON object per line on stdout: a record per phase (the backend that
actually ran, rows, compile seconds apart from run seconds, fallbacks,
equality with the host twin, peak device bytes), then as the LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Progress goes to stderr. The exit code is 0 only when that line says
``"ok": true``: the platform is ``tpu``, every phase ran on the device it
names, agreed with its twin, and ``diff.device.fallbacks`` stayed 0. A
phase that raises is recorded and the run goes on — and exits non-zero.

Sizes are arguments with the real sizes as defaults, so a CPU rehearsal
(``JAX_PLATFORMS=cpu`` and tiny sizes) walks every phase and ends in
``"ok": false`` for the right reasons: the platform is not ``tpu`` and auto
routing keeps XLA-CPU on ``host_native``.
"""

import argparse
import contextlib
import filecmp
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from unittest import mock

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
DS_PATH = "synth"

#: the environment of a host-twin run: every device route closed
HOST_TWIN_ENV = {
    "KART_DIFF_BACKEND": "host_native",
    "KART_DIFF_DEVICE": "0",
    "KART_DIFF_SHARDED": "0",
}

#: settings that would force a route: a run with any of them set cannot
#: stand for auto routing
ROUTING_OVERRIDES = (
    "KART_DIFF_BACKEND",
    "KART_DIFF_DEVICE",
    "KART_DIFF_SHARDED",
    "KART_DIFF_ENGINE",
    "KART_NO_JAX",
    "KART_DEVICE_BATCH_ROWS",
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="devices this run must find; 4 runs the mesh diff "
                   "and merge and no other phase")
    p.add_argument("--seed", type=int, default=0, help="seed of all data")
    p.add_argument("--rows", type=int, default=10_000_000,
                   help="rows of the diffed point layer (1%% edited)")
    p.add_argument("--cli-merge-rows", type=int, default=4_000_000,
                   help="rows of the repo `kart merge` runs on")
    p.add_argument("--cli-merge-conflicts", type=int, default=1_000)
    p.add_argument("--merge-rows", type=int, default=4_000_000,
                   help="rows of the blocks merge_classify runs on")
    p.add_argument("--merge-conflicts", type=int, default=1_000_000)
    p.add_argument("--envelopes", type=int, default=10_000_000,
                   help="envelopes of the spatial-filter scan")
    p.add_argument("--jsonl-rows", type=int, default=250_000,
                   help="changed rows of the json-lines materialise check")
    p.add_argument("--filtered-rows", type=int, default=8_000_000,
                   help="rows of the layer the filtered count runs on (enough "
                   "for the device route, which classifies the whole pair)")
    return p.parse_args(argv)


class CompileLog:
    """What jax compiled (or fetched from the persistent cache), heard
    through jax.monitoring: seconds, program names, cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = []
        self.cache_hits = 0

    def on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs.append(kwargs.get("fun_name"))

    def on_event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return self.seconds, len(self.programs), self.cache_hits

    def since(self, mark):
        seconds, n_programs, hits = mark
        return {
            "compile_seconds": round(self.seconds - seconds, 3),
            "programs": sorted(set(self.programs[n_programs:]) - {None}),
            "compile_cache_hits": self.cache_hits - hits,
        }


class Smoke:
    def __init__(self, args, work):
        self.args = args
        self.work = work
        self.ok = True
        self.t0 = time.perf_counter()
        self.compiles = CompileLog()
        self.n_traces = 0
        self.device = None  # jax.devices()[0] as the last line reports it
        self.diff_repo = None

    # -- reporting -----------------------------------------------------------

    def progress(self, msg):
        print(f"[{time.perf_counter() - self.t0:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def emit(self, rec):
        print(json.dumps(rec), flush=True)

    def fallbacks(self):
        from kart_tpu import telemetry as tm

        return sum(
            v for (name, _), v in tm.counters_snapshot().items()
            if name == "diff.device.fallbacks"
        )

    def peak_bytes(self):
        """peak_bytes_in_use per device, or None where the backend keeps no
        such figure (XLA-CPU)."""
        import jax

        stats = [d.memory_stats() for d in jax.devices()]
        if not all(s and "peak_bytes_in_use" in s for s in stats):
            return None
        return [int(s["peak_bytes_in_use"]) for s in stats]

    @contextlib.contextmanager
    def phase(self, name, **fields):
        """One record: what the body put in it, plus seconds, compiles,
        fallbacks and peak device bytes. ``rec["checks"]`` holds the named
        conditions; the phase is ok when none is false, nothing fell back
        and nothing raised."""
        rec = {"phase": name, **fields, "checks": {}}
        self.progress(f"{name}: start")
        t0 = time.perf_counter()
        mark = self.compiles.mark()
        fell_back = self.fallbacks()
        try:
            yield rec
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["seconds"] = round(time.perf_counter() - t0, 3)
        rec.update(self.compiles.since(mark))
        rec["fallbacks"] = self.fallbacks() - fell_back
        rec["peak_bytes_in_use"] = self.peak_bytes()
        rec["ok"] = (
            "error" not in rec
            and rec["fallbacks"] == 0
            and all(rec["checks"].values())
        )
        self.ok = self.ok and rec["ok"]
        self.emit(rec)
        self.progress(f"{name}: {'ok' if rec['ok'] else 'FAILED'}")

    # -- driving the program -------------------------------------------------

    def kart(self, args, env=None):
        """Run one `kart` command in this process, as the entry point a
        user calls. -> (click result, (wall seconds, its trace events))."""
        from click.testing import CliRunner

        from kart_tpu import telemetry as tm
        from kart_tpu.cli import cli

        self.n_traces += 1
        trace = os.path.join(self.work, f"trace-{self.n_traces}.json")
        tm.drain_events()
        tm.enable(trace=True, trace_path=trace)
        result, seconds = _timed(
            CliRunner().invoke, cli, args, env=env, catch_exceptions=False
        )
        events = []
        if os.path.exists(trace):
            with open(trace) as f:
                events = json.load(f)["traceEvents"]
        if result.exit_code != 0:
            raise RuntimeError(
                f"kart {' '.join(args)} exited {result.exit_code}: "
                f"{result.output[-500:]}"
            )
        return result, (seconds, events)

    @staticmethod
    def span_attr(events, span, attr):
        """The ``attr`` values of every ``span`` event, in order."""
        return [e["args"].get(attr) for e in events if e.get("name") == span]

    def routed(self, rec, span, dev, host):
        """What a device-vs-twin phase records of its two runs, each a
        (seconds, trace events) pair: the engine auto routing took (the
        ``backend=`` attribute of ``span``), the twin's, both wall times."""
        (rec["wall_seconds"], events), (rec["twin_wall_seconds"], twin) = dev, host
        rec["backend"] = self.span_attr(events, span, "backend")
        rec["twin_backend"] = self.span_attr(twin, span, "backend")
        want = "sharded_jax" if self.args.chips > 1 else "device_jax"
        rec["checks"]["backend"] = rec["backend"] == [want]
        rec["checks"]["twin_is_host"] = rec["twin_backend"] == ["host_native"]

    def mesh_stats(self):
        from kart_tpu.parallel.sharded_diff import STATS

        return dict(STATS)

    def mesh_checks(self, rec, stats_before, counter, events=()):
        """Four chips: the mesh path ran (its STATS counter rose; the diff
        dealt its batches onto four shards), and the arrays really landed on
        every device, not all on the first. Of the diff's rounds, how many
        went over as views of the sidecar's pages (recorded, not checked)."""
        from kart_tpu import telemetry as tm

        if self.args.chips == 1:
            return
        rec[counter] = self.mesh_stats()[counter] - stats_before[counter]
        rec["checks"]["mesh_ran"] = rec[counter] > 0
        if counter == "sharded_classify_calls":
            rec["device_shards"] = {
                name: value for name, _labels, value in tm.snapshot()["gauges"]
            }.get("diff.device.shards")
            rec["checks"]["shards"] = rec["device_shards"] == self.args.chips
            for attr in ("view_rounds", "rounds"):
                rec[attr] = self.span_attr(events, "diff.device.classify", attr)
        peaks = self.peak_bytes()
        rec["checks"]["every_device_held_data"] = bool(
            peaks and len(peaks) == self.args.chips and min(peaks) >= 1 << 20
        )


def _timed(fn, *args, **kwargs):
    """-> (fn's result, the wall seconds it took)."""
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    return result, round(time.perf_counter() - t0, 3)


# -- phases -------------------------------------------------------------------

def phase_native(smoke):
    """Build the host engine from native/*.cpp — never trust a .so that was
    lying in the tree: the host engine is what the chip is compared with, so
    which one ran is part of the result."""
    from kart_tpu import native

    with smoke.phase("native") as rec:
        rec["built"] = native.rebuild()
        rec["libkart_sf"] = native.load() is not None
        rec["libkart_io"] = native.load_io() is not None
        # no toolchain is not a failure of the chip: the twins are then
        # numpy, and the record says so. A library that loaded though this
        # run did not build it is of unknown source, and is a failure.
        rec["host_twin"] = (
            "native" if rec["libkart_sf"] and rec["libkart_io"] else "numpy"
        )
        rec["checks"]["built_from_source"] = rec["built"] or not (
            rec["libkart_sf"] or rec["libkart_io"]
        )


def phase_device(smoke):
    """Ask jax itself what it found — never the persisted probe verdict."""
    import jax

    from kart_tpu import runtime

    with smoke.phase("device") as rec:
        devices = jax.devices()
        smoke.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        rec.update(smoke.device)
        rec["jax"] = jax.__version__
        probe = runtime.probe_backend()
        rec["probe"] = {k: probe.get(k) for k in ("ok", "backend", "cached")}
        rec["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
        rec["routing_overrides"] = {
            k: os.environ[k] for k in ROUTING_OVERRIDES if k in os.environ
        }
        rec["checks"]["platform_is_tpu"] = smoke.device["platform"] == "tpu"
        rec["checks"]["device_count"] = len(devices) == smoke.args.chips
        rec["checks"]["probe_agrees"] = (
            bool(probe["ok"]) and probe["backend"] == smoke.device["platform"]
            and not probe.get("cached")
        )
        rec["checks"]["auto_routing"] = not rec["routing_overrides"]


def _changed_pks(path):
    """json-lines diff file -> (sorted changed pks, whether every line holds
    the values synth_repo wrote: rating pk/2 before the edit, pk after)."""
    pks, values_ok = [], True
    with open(path) as f:
        for line in f:
            obj = json.loads(line)
            if obj.get("type") != "feature":
                continue
            old, new = obj["change"]["-"], obj["change"]["+"]
            pks.append(new["fid"])
            values_ok = values_ok and (
                old["fid"] == new["fid"]
                and old["rating"] == new["fid"] / 2.0
                and new["rating"] == float(new["fid"])
            )
    return np.sort(np.asarray(pks, dtype=np.int64)), values_ok


def phase_diff(smoke):
    """BASELINE config 2 through `kart diff`: feature-count and json-lines
    under auto routing, against the same commands on the host engine and
    against what synth_repo says it edited."""
    from kart_tpu.synth import synth_repo

    args = smoke.args
    repo_path = os.path.join(smoke.work, "diff")
    with smoke.phase("diff.synth", rows=args.rows) as rec:
        _repo, info = synth_repo(
            repo_path, args.rows, edit_frac=0.01, seed=args.seed,
            blobs="changed", ds_path=DS_PATH, spatial=True,
        )
        rec["n_edits"] = info["n_edits"]
    smoke.diff_repo = repo_path
    base = ["-C", repo_path, "diff", "HEAD^...HEAD", "-o"]

    with smoke.phase("diff.feature_count", rows=args.rows) as rec:
        stats = smoke.mesh_stats()
        dev, dev_run = smoke.kart(base + ["feature-count"])
        host, host_run = smoke.kart(base + ["feature-count"], env=HOST_TWIN_ENV)
        smoke.routed(rec, "diff.classify", dev_run, host_run)
        rec["output"] = dev.stdout.strip()
        rec["checks"]["equals_twin"] = dev.stdout == host.stdout
        rec["checks"]["equals_synth"] = (
            f"{info['n_edits']} features changed" in dev.stdout
        )
        smoke.mesh_checks(rec, stats, "sharded_classify_calls", dev_run[1])

    with smoke.phase("diff.json_lines", rows=args.rows) as rec:
        dev_out = os.path.join(smoke.work, "diff-device.jsonl")
        host_out = os.path.join(smoke.work, "diff-host.jsonl")
        stats = smoke.mesh_stats()
        _, dev_run = smoke.kart(base + ["json-lines", "--output", dev_out])
        _, host_run = smoke.kart(
            base + ["json-lines", "--output", host_out], env=HOST_TWIN_ENV
        )
        smoke.routed(rec, "diff.classify", dev_run, host_run)
        pks, values_ok = _changed_pks(dev_out)
        rec["changed"] = len(pks)
        rec["checks"]["equals_twin"] = filecmp.cmp(dev_out, host_out, shallow=False)
        rec["checks"]["equals_synth"] = (
            np.array_equal(pks, info["edit_pks"]) and values_ok
        )
        smoke.mesh_checks(rec, stats, "sharded_classify_calls", dev_run[1])


def _merge_index(repo_path):
    """MERGE_INDEX of a repo in the merging state, as comparable values."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.merge.index import MergeIndex

    index = MergeIndex.read_from_repo(KartRepo(repo_path))
    return index.merged_tree, {
        label: tuple(
            (e.path, e.oid) if e is not None else None
            for e in (aot.ancestor, aot.ours, aot.theirs)
        )
        for label, aot in index.conflicts.items()
    }


def _padded_block(keys, oids):
    """Sorted (keys, oids) -> FeatureBlock padded to its bucket, as
    FeatureBlock.from_dataset hands the merge its blocks."""
    from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock, bucket_size

    n = len(keys)
    size = bucket_size(max(n, 1))
    padded_keys = np.full(size, PAD_KEY, dtype=np.int64)
    padded_keys[:n] = keys
    padded_oids = np.zeros((size, 5), dtype=np.uint32)
    padded_oids[:n] = oids
    return FeatureBlock(padded_keys, padded_oids, None, n)


def _merge_blocks(n, conflicts, seed):
    """(ancestor, ours, theirs) blocks of ``n`` rows: ``conflicts`` rows
    edited differently on both sides, conflicts/4 edited by theirs only,
    conflicts/4 by ours only, conflicts/8 deleted by theirs and conflicts/8
    inserted by theirs. -> (blocks, expected stats)."""
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    c, q, e = conflicts, conflicts // 4, conflicts // 8
    assert c + 2 * q + e <= n, "merge rows too few for that many conflicts"
    ours, theirs = oids.copy(), oids.copy()
    ours[:c, 0] ^= 1
    theirs[:c, 0] ^= 2
    theirs[c : c + q, 1] ^= 3
    ours[c + q : c + 2 * q, 2] ^= 5
    keep = n - e  # theirs deletes the last e rows ...
    new_keys = np.arange(n, n + e, dtype=np.int64)  # ... and inserts e rows
    new_oids = rng.integers(0, 2**32, size=(e, 5), dtype=np.uint32)
    blocks = (
        _padded_block(keys, oids),
        _padded_block(keys, ours),
        _padded_block(
            np.concatenate([keys[:keep], new_keys]),
            np.concatenate([theirs[:keep], new_oids]),
        ),
    )
    return blocks, {"conflicts": c, "take_theirs": q + 2 * e}


def phase_merge(smoke):
    """A 3-way `kart merge` of two conflicting branches at BASELINE config
    5's row count, and merge_classify on blocks with its million conflicts.
    Since PR 40 the CLI merge reads its three revisions from their sidecars
    (no feature tree is walked), so the layer is the full size; its
    conflicts stay few here because this phase writes them feature by
    feature (`commit_feature_edits`) — the million-conflict repository is
    the benchmark cell's (`merge4m.conflicts1m`, built in columns by
    benchmarks/layers/int_pk_merge_layer.py) — and the million-conflict
    classify is run at the kernel's own entry point."""
    from kart_tpu import telemetry as tm
    from kart_tpu.diff.backend import merge_classify
    from kart_tpu.synth import commit_feature_edits, synth_repo

    args = smoke.args
    repo_path = os.path.join(smoke.work, "merge")
    with smoke.phase("merge.synth", rows=args.cli_merge_rows) as rec:
        repo, _info = synth_repo(
            repo_path, args.cli_merge_rows, edit_frac=0, seed=args.seed,
            blobs="promised", ds_path=DS_PATH,
        )
        repo.refs.set("refs/heads/theirs", repo.head_commit_oid, "branch: smoke")
        c = args.cli_merge_conflicts
        first = (1 << 24) + args.cli_merge_rows  # past synth_repo's pks
        both = range(first, first + c)
        theirs_only = range(first + c, first + c + c // 2)
        commit_feature_edits(
            repo, DS_PATH, message="ours",
            inserts=[{"fid": pk, "rating": 1.0} for pk in both],
        )
        commit_feature_edits(
            repo, DS_PATH, message="theirs", ref="refs/heads/theirs",
            inserts=[{"fid": pk, "rating": 2.0} for pk in both]
            + [{"fid": pk, "rating": 3.0} for pk in theirs_only],
        )
        rec["conflicts"] = c

    with smoke.phase("merge.cli", rows=args.cli_merge_rows) as rec:
        stats = smoke.mesh_stats()
        dev, dev_run = smoke.kart(["-C", repo_path, "merge", "theirs"])
        dev_index = _merge_index(repo_path)
        smoke.kart(["-C", repo_path, "merge", "--abort"])
        _, host_run = smoke.kart(
            ["-C", repo_path, "merge", "theirs"], env=HOST_TWIN_ENV
        )
        host_index = _merge_index(repo_path)
        smoke.routed(rec, "diff.merge_classify", dev_run, host_run)
        rec["conflicts"] = len(dev_index[1])
        rec["checks"]["conflict_count"] = (
            len(dev_index[1]) == args.cli_merge_conflicts
            and f"{args.cli_merge_conflicts} conflicts" in dev.output
        )
        rec["checks"]["merge_index_equals_twin"] = dev_index == host_index
        smoke.mesh_checks(rec, stats, "sharded_classify_calls", dev_run[1])

    with smoke.phase(
        "merge.blocks", rows=args.merge_rows, conflicts=args.merge_conflicts
    ) as rec:
        blocks, expected = _merge_blocks(
            args.merge_rows, args.merge_conflicts, args.seed
        )
        stats = smoke.mesh_stats()

        def timed():
            tm.drain_events()
            result, seconds = _timed(merge_classify, *blocks)
            return result, (seconds, tm.drain_events())

        dev, dev_run = timed()
        with mock.patch.dict(os.environ, HOST_TWIN_ENV):
            host, host_run = timed()
        smoke.routed(rec, "diff.merge_classify", dev_run, host_run)
        rec["stats"] = dev[3]
        rec["checks"]["equals_twin"] = dev[3] == host[3] and all(
            np.array_equal(a, b) for a, b in zip(dev[:3], host[:3])
        )
        rec["checks"]["equals_expected"] = dev[3] == expected
        smoke.mesh_checks(rec, stats, "sharded_classify_calls", dev_run[1])


def _grid_envelopes(n):
    """(n, 4) f64 wsen envelopes laid out like synth_repo's, snapped to a
    2^-13 degree grid: every coordinate, and every difference of two, is
    exact in float32, so the f32 device kernel and the f64 host scan must
    agree on every row — a mismatch is a fault, never a rounding."""
    from kart_tpu.synth import synth_envelopes

    env = synth_envelopes(np.arange(n, dtype=np.int64)).astype(np.float64)
    return np.round(env * 8192.0) / 8192.0


def phase_bbox(smoke):
    """The spatial-filter envelope scan as blob_filter_for_spec calls it:
    the padded query, one-shot and with a cache_key."""
    import jax

    from kart_tpu import native
    from kart_tpu.ops import bbox

    n = smoke.args.envelopes
    with smoke.phase("bbox", envelopes=n) as rec:
        compiled_before = len(smoke.compiles.programs)
        env = _grid_envelopes(n)
        w, s, e, nn = 100.0, -40.0, 140.0, 10.0
        pad = 1e-4  # spatial_filter's fail-open pad against f32 rounding
        query = (w - pad, s - pad, e + pad, nn + pad)
        # the twin scans with the query the kernel is given: rounded to f32
        q32 = np.asarray(query, dtype=np.float32).astype(np.float64)
        twin, rec["twin_wall_seconds"] = _timed(native.bbox_intersects, env, q32)
        exact = native.bbox_intersects(env, (w, s, e, nn))

        one_shot, rec["one_shot_wall_seconds"] = _timed(
            bbox.bbox_intersects, env, query
        )
        key = ("chip_smoke", n)
        first, rec["resident_first_wall_seconds"] = _timed(
            bbox.bbox_intersects, env, query, cache_key=key
        )
        again, rec["resident_repeat_wall_seconds"] = _timed(
            bbox.bbox_intersects, env, query, cache_key=key
        )

        rec["hits"] = int(one_shot.sum())
        entry = bbox._RESIDENT_CACHE.get(key)
        rec["resident_on"] = (
            sorted({d.platform for d in entry[0].devices()})
            if entry is not None and isinstance(entry[0], jax.Array)
            else None
        )
        rec["checks"]["equals_twin"] = (
            np.array_equal(one_shot, twin)
            and np.array_equal(first, twin)
            and np.array_equal(again, twin)
        )
        # what the pad is for: no feature the exact query matches is vetoed
        rec["checks"]["conservative"] = bool(np.all(one_shot[exact]))
        rec["checks"]["some_hits"] = 0 < rec["hits"] < n
        # on the chip the scan is the Pallas kernel: no jnp route in silence
        programs = smoke.compiles.programs[compiled_before:]
        rec["checks"]["pallas_route"] = (
            any("_bbox_pallas_inner_core" in str(p) for p in programs)
            and not any("_bbox_intersects_jnp_core" in str(p) for p in programs)
            and rec["resident_on"] == ["tpu"]
        )


def phase_materialise(smoke):
    """`-o json-lines` makes its lines in native calls on a pool of threads
    — here after this process has touched the device. The bytes must equal
    the delta path's (KART_FUSED_JSONL=0), every row must have been made
    natively, and on a host with more than one core more than one thread
    must have made them: each leaves its `serialise.chunk` spans in the
    trace under its own thread id."""
    from kart_tpu.synth import synth_repo

    n = smoke.args.jsonl_rows
    with smoke.phase("materialise", rows=n) as rec:
        repo_path = os.path.join(smoke.work, "materialise")
        synth_repo(
            repo_path, n, edit_frac=1.0, seed=smoke.args.seed,
            blobs="changed", ds_path=DS_PATH, spatial=True,
        )
        fused_out = os.path.join(smoke.work, "materialise-fused.jsonl")
        delta_out = os.path.join(smoke.work, "materialise-delta.jsonl")
        cmd = ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json-lines"]
        _, (rec["wall_seconds"], events) = smoke.kart(
            cmd + ["--output", fused_out]
        )
        smoke.kart(cmd + ["--output", delta_out], env={"KART_FUSED_JSONL": "0"})
        chunks = [e for e in events if e.get("name") == "serialise.chunk"]
        rec["chunk_threads"] = len({e["tid"] for e in chunks})
        rec["native_rows"] = sum(e["args"].get("native_rows", 0) for e in chunks)
        rec["checks"]["all_rows_native"] = rec["native_rows"] == n
        rec["checks"]["threads"] = rec["chunk_threads"] >= min(
            2, os.cpu_count() or 1, len(chunks)
        )
        rec["checks"]["equals_delta_path"] = filecmp.cmp(
            fused_out, delta_out, shallow=False
        )


def _churn_blocks(builder, branch, params, seed):
    """(old, new) unpadded blocks with the key columns the benchmark's
    republish builder gives the sidecars of its commit ``branch``, and oids
    that differ on its updates."""
    from kart_tpu.ops.blocks import FeatureBlock

    edits = builder.edit_sets(params, seed)[branch]
    old_keys, keep, inserted = builder.key_columns(params, edits)
    rng = np.random.default_rng(seed)
    old_oids = rng.integers(0, 2**32, size=(len(old_keys), 5), dtype=np.uint32)
    new_oids = old_oids.copy()
    new_oids[edits[0], 4] ^= 1  # the updated rows
    new_keys = np.concatenate([old_keys[keep], inserted])
    new_oids = np.concatenate(
        [new_oids[keep], rng.integers(0, 2**32, size=(len(inserted), 5), dtype=np.uint32)]
    )
    return (
        FeatureBlock(old_keys, old_oids, None, len(old_keys)),
        FeatureBlock(new_keys, new_oids, None, len(new_keys)),
    )


def phase_churn(smoke):
    """The republish deployment's two commits (benchmarks/configs/
    baseline2_points_10m_churn.json: uniform updates + deletes + appended
    inserts; one contiguous run deleted) through ``classify_blocks`` under
    auto routing: classes and counts equal the host engine's, and every
    key-range chunk's tile census (``dense_tiles``, ``overflow_tiles`` of
    its ``diff.device.kernel`` span) equals a numpy recount over that
    chunk's rows. Uniform churn must stay on the windowed join in every
    chunk; the bulk delete must send exactly one chunk a call, the one its
    hole is in, to the sort-join."""
    from kart_tpu import telemetry as tm
    from kart_tpu.ops.blocks import FeatureBlock
    from kart_tpu.ops.diff_kernel import (
        classify_blocks,
        classify_blocks_host,
        classify_chunk_plan,
        join_census_reference,
    )

    builder = _bench_module("layers", "int_pk_churn_layer")
    config = os.path.join(
        REPO_ROOT, "benchmarks", "configs", "baseline2_points_10m_churn.json"
    )
    with open(config) as f:
        params = dict(json.load(f)["layer"]["params"], rows=smoke.args.rows)

    for branch in builder.BRANCHES:
        with smoke.phase(f"churn.{branch}", rows=params["rows"]) as rec:
            old, new = _churn_blocks(builder, branch, params, smoke.args.seed)
            tm.drain_events()
            tm.enable(trace=True)
            try:
                (old_class, new_class, counts), rec["wall_seconds"] = _timed(
                    classify_blocks, old, new
                )
                again, rec["second_wall_seconds"] = _timed(classify_blocks, old, new)
                kernels = [
                    e["args"] for e in tm.drain_events()
                    if e.get("name") == "diff.device.kernel"
                ]
            finally:
                tm.enable(trace=False)
            host_old, host_new, host_counts = classify_blocks_host(old, new)
            rec["counts"] = counts
            rec["kernel_spans"] = kernels
            plan = classify_chunk_plan(old, new)
            rec["chunks"] = len(plan)
            # two calls: a kernel span a chunk each
            rec["checks"]["ran_on_device"] = len(kernels) == 2 * len(plan)
            rec["checks"]["equals_host_engine"] = (
                np.array_equal(old_class, host_old)
                and np.array_equal(new_class, host_new)
                and counts == host_counts
                and np.array_equal(again[0], host_old)
                and np.array_equal(again[1], host_new)
            )
            rec["census_recount"] = [
                join_census_reference(
                    *(
                        FeatureBlock(b.keys[lo:hi], b.oids[lo:hi], None, hi - lo)
                        for b, (lo, hi) in ((old, old_rows), (new, new_rows))
                    ),
                    sizes,
                )
                for old_rows, new_rows, sizes in plan
            ]
            rec["checks"]["census_equals_recount"] = [
                (k.get("dense_tiles"), k.get("overflow_tiles")) for k in kernels
            ] == 2 * rec["census_recount"]
            sorted_chunks = [k.get("chunk", 0) for k in kernels if k.get("join") == "sort"]
            if branch == "churn":
                rec["checks"]["windowed_join_answered"] = all(
                    k.get("join") == "window" for k in kernels
                )
            else:
                # the hole's chunk, in each of the two calls, and no other
                rec["checks"]["one_chunk_to_the_sort_join"] = (
                    len(sorted_chunks) == 2
                    and len(set(sorted_chunks)) == 1
                    and len(plan) > 1
                )


def _bench_module(kind, name):
    """benchmarks/<kind>/<name>.py, loaded as benchmarks/run.py loads it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}",
        os.path.join(REPO_ROOT, "benchmarks", kind, name + ".py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


#: the attributes the filtered count's spans carry on one chip, where the
#: census picks the changed-rows route (docs/OBSERVABILITY.md)
FILTERED_SPANS = {
    "diff.prefilter": ("rows",),
    "diff.prefilter.census": ("blocks", "bound_share"),
    "diff.prefilter.changed": ("rows", "survivors"),
    "diff.classify": ("rows", "backend", "counts_only", "input_bytes", "resident_bytes"),
    "diff.refine": ("candidates", "inside", "outside", "residue", "blobs_read"),
}


def phase_filtered(smoke):
    """The filtered-clone deployment (benchmarks/configs/
    baseline4_nodes_10m_filtered.json) through its cell's command: `kart diff
    -o feature-count` in a repository with the configuration's polygonal
    spatial filter set, under auto routing — the count against the
    benchmark's reference (the edited points inside the polygon, by its own
    ray cast) and against the host engine's, and every stage's span with its
    attributes."""
    builder = _bench_module("layers", "nodes_filtered_layer")
    reference = _bench_module("references", "feature_count_filtered")
    config = os.path.join(
        REPO_ROOT, "benchmarks", "configs", "baseline4_nodes_10m_filtered.json"
    )
    with open(config) as f:
        params = dict(json.load(f)["layer"]["params"], rows=smoke.args.filtered_rows)

    with smoke.phase("filtered.layer", rows=params["rows"]) as rec:
        base = os.path.join(smoke.work, "filtered-base")
        os.makedirs(base)
        builder.build_base(base, params)
        repo_path, info = builder.add_edit_commit(
            base, os.path.join(smoke.work, "filtered"), params, smoke.args.seed
        )
        rec["n_edits"] = info["n_edits"]

    with smoke.phase("filtered.feature_count", rows=params["rows"]) as rec:
        command = ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "feature-count"]
        dev, dev_run = smoke.kart(command)
        host, host_run = smoke.kart(command, env=HOST_TWIN_ENV)
        smoke.routed(rec, "diff.classify", dev_run, host_run)
        rec["output"] = dev.stdout.strip()
        rec["edits_in_polygon"] = reference.edits_in_polygon(info)
        rec["edits_in_box"] = info["n_edits_in_box"]
        rec["checks"]["equals_twin"] = dev.stdout == host.stdout
        rec["checks"].update(reference.check(dev.stdout_bytes, info))
        spans = {e["name"]: e.get("args", {}) for e in dev_run[1] if e.get("ph") == "X"}
        rec["spans"] = {
            name: {a: spans.get(name, {}).get(a) for a in attrs}
            for name, attrs in FILTERED_SPANS.items()
        }
        rec["checks"]["spans_carry_their_attributes"] = all(
            set(attrs) <= set(spans.get(name, ())) for name, attrs in FILTERED_SPANS.items()
        )


def phase_one_device_mesh(smoke):
    """The device programs auto routing cannot reach on one chip —
    routing.mesh_open wants two devices — each run once on a one-device mesh
    against its host twin, at one production batch."""
    from kart_tpu import telemetry as tm
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff import backend as B
    from kart_tpu.diff import sidecar
    from kart_tpu.diff.device_batch import classify_blocks_batched
    from kart_tpu.geom import (
        DEFAULT_GEOM_BATCH_ROWS,
        boxes_vertex_column,
        refine_pairs_host,
    )
    from kart_tpu.routing import DEVICE_MIN_ENVELOPES
    from kart_tpu.ops.blocks import FeatureBlock
    from kart_tpu.ops.diff_kernel import classify_blocks_host
    from kart_tpu.parallel.mesh import make_mesh
    from kart_tpu.query.join import TILE_ROWS
    from kart_tpu.query.scan import DEFAULT_BATCH_ROWS
    from kart_tpu.synth import synth_envelopes
    from kart_tpu.tiles.clip import quantize_from_merc

    host = B.BACKENDS["host_native"]
    repo = KartRepo(smoke.diff_repo)
    old_block, new_block = (
        sidecar.load_block(repo, repo.structure(ref).datasets[DS_PATH], pad=False)
        for ref in ("HEAD^", "HEAD")
    )

    def envelopes(n):
        return synth_envelopes(np.arange(n, dtype=np.int64))

    with smoke.phase("mesh1.classify_batched", rows=old_block.count) as rec:
        twin = classify_blocks_host(old_block, new_block)
        got, rec["wall_seconds"] = _timed(
            classify_blocks_batched, old_block, new_block, mesh=make_mesh(1)
        )
        rec["counts"] = got[2]
        rec["checks"]["equals_twin"] = (
            got[2] == twin[2]
            and np.array_equal(got[0], twin[0])
            and np.array_equal(got[1], twin[1])
        )

    with smoke.phase("mesh1.sampled_counts_pmapped") as rec:
        # every 32nd row: the size of a `veryfast` estimate's sample (2/64)
        subs = [
            FeatureBlock(
                np.ascontiguousarray(b.keys[: b.count : 32]),
                np.ascontiguousarray(b.oids[: b.count : 32]),
                None,
                len(range(0, b.count, 32)),
            )
            for b in (old_block, new_block)
        ]
        rec["rows"] = subs[0].count
        twin = classify_blocks_host(*subs)[2]
        rec["counts"], rec["wall_seconds"] = _timed(B.sampled_counts_pmapped, *subs)
        rec["checks"]["equals_twin"] = rec["counts"] == twin

    with smoke.phase("mesh1.envelope_hits", rows=old_block.count) as rec:
        query = np.asarray((100.0, -40.0, 140.0, 10.0), dtype=np.float64)
        twin = host.envelope_hits(old_block, query)
        got, rec["wall_seconds"] = _timed(
            B.sharded_envelope_hits, old_block.envelopes, old_block.count, query
        )
        rec["hits"] = int(got.sum())
        rec["checks"]["equals_twin"] = np.array_equal(got, twin)
        rec["checks"]["some_hits"] = 0 < rec["hits"] < old_block.count

    # the smallest batch the tile exporter would send to a device
    with smoke.phase("mesh1.merc_envelopes", rows=DEVICE_MIN_ENVELOPES) as rec:
        env = envelopes(DEVICE_MIN_ENVELOPES).astype(np.float64)
        twin = host.merc_envelopes(env)
        got, rec["wall_seconds"] = _timed(B.sharded_merc_envelopes, env)
        rec["max_abs_error"] = float(
            max(np.max(np.abs(g - t)) for g, t in zip(got, twin))
        )
        # the repo's own contract (tiles/clip.py): device transcendentals
        # need not be bit-identical, the quantized tile integers must be
        for z in (0, 4, 11, 18):
            x = y = (1 << z) // 2
            rec["checks"][f"quantized_equal_z{z}"] = np.array_equal(
                quantize_from_merc(env, got, z, x, y),
                quantize_from_merc(env, twin, z, x, y),
            )

    # one `kart query` join batch: a probe batch against one build tile
    with smoke.phase(
        "mesh1.join_counts", probe=DEFAULT_BATCH_ROWS, build=TILE_ROWS
    ) as rec:
        probe = envelopes(DEFAULT_BATCH_ROWS)
        # build boxes two degrees wide over the same ground: real overlaps
        build = probe[:: DEFAULT_BATCH_ROWS // TILE_ROWS].copy()
        build[:, 2:] += np.float32(2.0)
        twin = host.join_counts(build, probe)
        got, rec["wall_seconds"] = _timed(B.sharded_join_counts, build, probe)
        rec["pairs"] = got[1]
        rec["checks"]["equals_twin"] = (
            got[1] == twin[1] and np.array_equal(got[0], twin[0])
        )
        rec["checks"]["some_pairs"] = got[1] > len(build)

    # one exact-refine round of box polygons
    with smoke.phase("mesh1.refine_pairs", pairs=DEFAULT_GEOM_BATCH_ROWS) as rec:
        env = envelopes(DEFAULT_GEOM_BATCH_ROWS).astype(np.float64)
        moved = env.copy()
        # every other box slides off its partner; the rest still touch it
        moved[::2] += 0.01
        moved[1::2] += 0.0005
        col_a, col_b = boxes_vertex_column(env), boxes_vertex_column(moved)
        idx = np.arange(len(env), dtype=np.int64)
        twin = refine_pairs_host(col_a, idx, col_b, idx)
        got, rec["wall_seconds"] = _timed(
            B.sharded_refine_pairs, col_a, idx, col_b, idx
        )
        rec["intersecting"] = int(got.sum())
        rec["checks"]["equals_twin"] = np.array_equal(got, twin)
        rec["checks"]["some_of_each"] = 0 < rec["intersecting"] < len(idx)

    tm.drain_events()


def run(args, work):
    import jax

    from kart_tpu import telemetry as tm

    smoke = Smoke(args, work)
    jax.monitoring.register_event_duration_secs_listener(smoke.compiles.on_duration)
    jax.monitoring.register_event_listener(smoke.compiles.on_event)
    tm.enable(metrics=True)

    phase_native(smoke)
    phase_device(smoke)
    phase_diff(smoke)
    phase_merge(smoke)
    if args.chips == 1:
        phase_bbox(smoke)
        phase_materialise(smoke)
        phase_churn(smoke)
        phase_filtered(smoke)
        phase_one_device_mesh(smoke)

    with smoke.phase("summary") as rec:
        rec["total_seconds"] = round(time.perf_counter() - smoke.t0, 1)
        rec["fallbacks_total"] = smoke.fallbacks()
        rec["checks"]["no_fallbacks"] = rec["fallbacks_total"] == 0
    return smoke


def main(argv=None):
    args = parse_args(argv)
    # a measurement probes for itself: a verdict some earlier process
    # persisted must not be able to answer for this machine's accelerator
    os.environ["KART_PROBE_CACHE"] = "0"
    work = tempfile.mkdtemp(prefix="kart-chip-smoke-")
    try:
        smoke = run(args, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": smoke.ok, "device": smoke.device}), flush=True)
    return 0 if smoke.ok else 1


if __name__ == "__main__":
    sys.exit(main())
