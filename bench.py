"""North-star benchmark: features diffed/sec, device vs CPU reference path.

Builds two synthetic revisions of an N-row layer (default 10M, BASELINE.json
config #2: attribute-only diff), runs the jitted diff-classification kernel
on the live device, and compares against the pure-numpy reference
implementation of identical semantics (the measured CPU baseline — the
reference publishes no absolute numbers, SURVEY.md §6).

The device-side inputs are *generated on device* (jitted PRNG) — benchmarks
must not pay a ~600MB host->device transfer that the real pipeline streams
and double-buffers.

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
"""

import itertools
import json
import os
import re
import time

import numpy as np

CHANGE_STRIDE = 100  # 1 row in 100 gets new oids: 1% attribute updates


def _build_np(n):
    """Host-side (numpy) copy of the same synthetic revisions, for the CPU
    baseline measurement."""
    from kart_tpu.ops.blocks import bucket_size, PAD_KEY
    from kart_tpu.parallel.sharded_diff import synthetic_block

    old = synthetic_block(n, seed=0)
    new = synthetic_block(n, seed=0)
    idx = np.arange(7, n, CHANGE_STRIDE)
    new_oids = new.oids.copy()
    rng = np.random.default_rng(7)
    new_oids[idx] = rng.integers(0, 2**32, size=(len(idx), 5), dtype=np.uint32)
    new.oids = new_oids
    return old, new, len(idx)


def _device_args(n):
    """Generate both revisions on device: keys 0..n-1 (padded), random oids,
    every CHANGE_STRIDE-th row's oids differing between old and new."""
    import jax
    import jax.numpy as jnp

    from kart_tpu.ops.blocks import bucket_size, PAD_KEY

    size = bucket_size(max(n, 1))

    @jax.jit
    def gen():
        idx = jnp.arange(size, dtype=jnp.int64)
        keys = jnp.where(idx < n, idx, PAD_KEY)
        old_oids = jax.random.bits(
            jax.random.PRNGKey(0), (size, 5), jnp.uint32
        )
        changed_oids = jax.random.bits(
            jax.random.PRNGKey(1), (size, 5), jnp.uint32
        )
        is_changed = (idx % CHANGE_STRIDE == 7) & (idx < n)
        new_oids = jnp.where(is_changed[:, None], changed_oids, old_oids)
        return keys, old_oids, new_oids

    keys, old_oids, new_oids = gen()
    n_changed = len(range(7, n, CHANGE_STRIDE))
    return (keys, old_oids, keys, new_oids, n, n), n_changed


def main():
    """Watchdog wrapper: run the measurement in a ``--worker`` subprocess
    with a hard timeout and print its last complete record. This parent
    stays off jax — a chip belongs to one process at a time, and the worker
    is the one that needs it. A measurement does not fall back: with no
    accelerator, or a worker that printed no record, this exits non-zero
    and prints nothing."""
    import subprocess
    import sys

    timeout_s = int(os.environ.get("KART_BENCH_TIMEOUT", 2400))
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]

    def last_json_line(stdout):
        """Last line of (possibly truncated) worker output that parses as
        JSON — a worker killed mid-print leaves a fragment after the last
        complete record."""
        if not stdout:
            return None
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        for line in reversed(stdout.strip().splitlines()):
            if not line.startswith("{"):
                continue
            try:
                json.loads(line)
            except ValueError:
                continue
            return line
        return None

    def run_worker(env=None):
        """-> (last complete JSON record or None, returncode or None),
        salvaging partial output on timeout or crash (the worker prints a
        full record before the long 100M tail; the probe-failure exit — rc 3
        — prints no JSON, so any parseable record is a real measurement).
        returncode None means the subprocess hit the watchdog timeout."""
        try:
            proc = subprocess.run(
                cmd, timeout=timeout_s, capture_output=True, text=True, env=env
            )
        except subprocess.TimeoutExpired as e:
            return last_json_line(e.stdout), None
        line = last_json_line(proc.stdout)
        if line is None and proc.stderr:
            print(proc.stderr.strip()[-2000:], file=sys.stderr)
        return line, proc.returncode

    env = dict(os.environ)
    # a measurement probes for itself: a verdict some earlier process
    # persisted must not be able to answer for this machine's accelerator
    env["KART_PROBE_CACHE"] = "0"
    # a benchmark can afford a far bigger backend-init budget than an
    # interactive CLI's 75 s default — scale it with the bench timeout
    # unless the operator pinned it explicitly
    if "KART_JAX_INIT_TIMEOUT" not in env:
        env["KART_JAX_INIT_TIMEOUT"] = str(min(300, max(120, timeout_s // 8)))
    line, rc = run_worker(env)
    if not line:
        sys.exit(rc or 1)
    print(line)


def worker():
    n = int(os.environ.get("KART_BENCH_ROWS", 10_000_000))
    reps = int(os.environ.get("KART_BENCH_REPS", 5))

    import sys

    from kart_tpu.runtime import probe_backend

    import datetime as _dt

    probe_attempts = [_dt.datetime.now(_dt.timezone.utc).isoformat(timespec="seconds")]
    info = probe_backend()
    if not info["ok"] and "timed out" in (info.get("error") or ""):
        # distinguish slow-vs-wedged before giving up: wait once more on the
        # abandoned init thread (KART_JAX_REPROBE=0 disables — retry attempts
        # must fail fast)
        if os.environ.get("KART_JAX_REPROBE") != "0":
            from kart_tpu.runtime import reprobe

            probe_attempts.append(
                _dt.datetime.now(_dt.timezone.utc).isoformat(timespec="seconds")
            )
            info = reprobe(120)
    if not info["ok"]:
        # backend unusable: exit non-zero and print no record
        print(f"backend probe failed: {info['error']}", file=sys.stderr)
        sys.exit(3)

    import jax

    if jax.devices()[0].platform == "cpu":
        # a host-CPU run is not a measurement of this system: no record
        print("no accelerator: jax found only the CPU platform", file=sys.stderr)
        sys.exit(3)

    from kart_tpu.ops.diff_kernel import (
        _classify_padded,
        classify_blocks_reference,
    )

    # --- CPU baseline: numpy implementation of identical semantics.
    # Measured on a slice and scaled (searchsorted is O(n log n); the scale
    # error is in the baseline's favour).
    base_n = min(n, 2_000_000)
    b_old, b_new, _ = _build_np(base_n)
    t0 = time.perf_counter()
    classify_blocks_reference(b_old, b_new)
    cpu_s = time.perf_counter() - t0
    cpu_rate = base_n / cpu_s

    # --- reference-equivalent baseline: the hot loop the reference actually
    # runs (rich_base_dataset.py:205-300 — per-feature Python: decode the
    # path to a pk, compare oids, build a delta record). Our numpy twin
    # above is a far *stricter* baseline than the reference's loop.
    ref_rate = _reference_loop_rate(b_old, b_new, min(base_n, 300_000))

    # --- the production HOST engine (native C++ merge-join): what the cost
    # model actually routes CPU deployments to — so even a CPU-fallback
    # record carries the real production-vs-reference win. Measured like
    # the device path: at full n, warmed, averaged over reps (on a CPU
    # fallback this rate IS the headline).
    from kart_tpu.ops.diff_kernel import classify_blocks_host

    h_old, h_new, _ = _build_np(n) if n != base_n else (b_old, b_new, None)
    classify_blocks_host(h_old, h_new)  # warmup: native lib load, first touch
    t0 = time.perf_counter()
    for _ in range(reps):
        classify_blocks_host(h_old, h_new)
    host_rate = n / ((time.perf_counter() - t0) / reps)

    # --- device path: the sort-join, the one kernel every backend can run
    # (on XLA-CPU routing never picks it: the headline there is host_rate)
    kernel = _classify_padded
    args, n_changed = _device_args(n)
    jax.block_until_ready(args)

    out = kernel(*args)  # warmup / compile
    jax.block_until_ready(out)
    counts = np.asarray(out[3])
    assert counts[1] == n_changed, (
        f"bad diff: {counts.tolist()} != {n_changed} updates"
    )

    t0 = time.perf_counter()
    for _ in range(reps):
        out = kernel(*args)
    jax.block_until_ready(out)
    dev_s = (time.perf_counter() - t0) / reps
    dev_rate = n / dev_s

    cli = _cli_diff_bench()
    merge = _merge_bench()
    bbox = _bbox_bench()
    est = _estimation_bench()
    resume = _fetch_resume_bench()
    telem = _telemetry_overhead_bench()
    lint = _lint_bench()

    # The headline value is the rate of the engine `classify_blocks` would
    # actually route to on this backend (VERDICT r4 weak #5): the native
    # host merge-join on XLA-CPU fallback (kart_tpu.routing sends CPU
    # backends to it at every size), the device kernel on an accelerator.
    # The unrouted kernel rate stays as a secondary key.
    routed_rate = host_rate if info["backend"] == "cpu" else dev_rate
    record = {
        "metric": "features_diffed_per_sec_10M_attr_diff",
        "value": round(routed_rate),
        "unit": "features/s",
        # BASELINE.json's CPU baseline is the *reference's* measured
        # per-feature hot loop (SURVEY §6: "must be measured, not
        # copied"); the numpy vectorized twin is our own far
        # stricter implementation, reported alongside
        "vs_baseline": round(routed_rate / ref_rate, 1),
        "vs_numpy_twin": round(routed_rate / cpu_rate, 2),
        "device_kernel_rate": round(dev_rate),
        "backend": info["backend"],
        "device_kind": info["device_kind"],
        "n_devices": info["n_devices"],
        "backend_init_seconds": info["init_seconds"],
        # when the probe needed a second wait, these timestamps show each
        # attempt
        "backend_probe_attempts_utc": probe_attempts,
        "backend_probe_error": info.get("error"),
        "numpy_twin_rate": round(cpu_rate),
        "reference_loop_rate": round(ref_rate),
        "host_native_rate": round(host_rate),
        "host_native_vs_reference": round(host_rate / ref_rate, 1),
        **cli,
        **merge,
        **bbox,
        **est,
        **resume,
        **telem,
        **lint,
    }
    # the polygon and 100M sections are the long tail (synth + multi-minute
    # diffs): print the record BEFORE each so a watchdog timeout mid-section
    # still salvages every earlier number (main() keeps the last complete
    # line), then print the augmented record as each completes
    print(json.dumps(record), flush=True)
    imp10 = _import_10m_bench()
    if imp10:
        record.update(imp10)
        print(json.dumps(record), flush=True)
    poly = _cli_polygon_diff()
    if poly:
        record.update(poly)
        print(json.dumps(record), flush=True)
    big = _cli_diff_100m()
    if big:
        record.update(big)
        print(json.dumps(record), flush=True)


def _reference_loop_rate(b_old, b_new, slice_n):
    """Features/s of a faithful re-creation of the reference's per-feature
    diff loop (kart/rich_base_dataset.py:205-300): walk the tree-diff
    entries in Python, decode each path's filename to a pk (urlsafe-b64 +
    msgpack, exactly what decode_path_to_1pk does), compare blob ids, and
    build a delta record. Measured on a slice and scaled linearly (the loop
    is O(n))."""
    import base64

    from kart_tpu.core.serialise import msg_unpack
    from kart_tpu.models.paths import PathEncoder

    enc = PathEncoder.INT_PK_ENCODER
    keys = b_old.keys[:slice_n]
    paths = enc.encode_paths_batch(keys)
    filenames = [p.rsplit("/", 1)[-1] for p in paths]
    old_oids = [bytes(o) for o in b_old.oids[:slice_n]]
    new_oids = [bytes(o) for o in b_new.oids[:slice_n]]

    t0 = time.perf_counter()
    deltas = []
    for fname, o_oid, n_oid in zip(filenames, old_oids, new_oids):
        pk = msg_unpack(base64.urlsafe_b64decode(fname + "=="))
        if o_oid != n_oid:
            deltas.append((pk, "update", o_oid, n_oid))
    dt = time.perf_counter() - t0
    return slice_n / dt


def _bbox_bench():
    """BASELINE config #4: the spatially-filtered diff's bbox prefilter —
    one query rectangle against N feature envelopes (Pallas on TPU, XLA
    elsewhere) vs the numpy reference. Returns {} on any failure."""
    import sys

    try:
        rows = int(os.environ.get("KART_BENCH_BBOX_ROWS", 10_000_000))
        if rows <= 0:
            return {}
        import numpy as np

        import jax

        from kart_tpu.ops.bbox import (
            bbox_intersects_jnp,
            bbox_intersects_np,
            bbox_intersects_pallas,
            pad_envelopes,
        )
        from kart_tpu.runtime import default_backend

        rng = np.random.default_rng(0)
        env = np.stack(
            [
                rng.uniform(-180, 179, rows),
                rng.uniform(-90, 89, rows),
                rng.uniform(-180, 180, rows),
                rng.uniform(-90, 90, rows),
            ],
            axis=1,
        )
        env[:, 2] = np.maximum(env[:, 2], env[:, 0])
        env[:, 3] = np.maximum(env[:, 3], env[:, 1])
        query = np.asarray((-20.0, -20.0, 40.0, 30.0), dtype=np.float32)

        t0 = time.perf_counter()
        ref = bbox_intersects_np(env, query)
        np_s = time.perf_counter() - t0

        w, s, e, n, count = pad_envelopes(env)
        kernel = (
            bbox_intersects_pallas
            if default_backend() == "tpu"
            else bbox_intersects_jnp
        )
        mask = kernel(w, s, e, n, query)  # compile + warm
        got = np.asarray(mask)[:count]
        assert (got == ref).all()

        # end-to-end (host arrays in, host mask out: one partial-clone pass)
        t0 = time.perf_counter()
        got = np.asarray(kernel(w, s, e, n, query))
        e2e_s = time.perf_counter() - t0

        # kernel-only (device-resident envelopes, e.g. a repeatedly-queried
        # table): excludes the host->HBM transfer
        dw, ds_, de, dn = (jax.device_put(a) for a in (w, s, e, n))
        jax.block_until_ready((dw, ds_, de, dn))
        np.asarray(kernel(dw, ds_, de, dn, query))  # warm resident shapes
        t0 = time.perf_counter()
        for _ in range(3):
            mask = kernel(dw, ds_, de, dn, query)
        np.asarray(mask)
        dev_s = (time.perf_counter() - t0) / 3

        # the production resident-cache path (VERDICT r2 weak #3): first
        # call uploads + caches, second call must beat numpy
        from kart_tpu.ops.bbox import bbox_intersects

        key = ("bench-bbox", rows)
        got = bbox_intersects(env, query, cache_key=key)  # upload + warm
        assert (got == ref).all()
        t0 = time.perf_counter()
        got = bbox_intersects(env, query, cache_key=key)
        resident_s = time.perf_counter() - t0
        assert (got == ref).all()

        # the native branchless f32 scan (the sidecar-envelope residue path,
        # commit 6d59450) and the packed 20-bit reference-format path,
        # recorded so the headline f32 claim is reproducible (VERDICT r5 #5)
        from kart_tpu import native as _native

        env32 = env.astype(np.float32)
        ref32 = bbox_intersects_np(env32.astype(np.float64), query)
        got32 = _native.bbox_intersects_f32(env32, query)
        assert (got32 == ref32).all()
        t0 = time.perf_counter()
        for _ in range(3):
            _native.bbox_intersects_f32(env32, query)
        f32_s = (time.perf_counter() - t0) / 3

        from kart_tpu.ops.envelope_codec import EnvelopeCodec

        packed = EnvelopeCodec().encode_batch(env)
        _native.filter_packed(packed, query)  # warm (page in)
        t0 = time.perf_counter()
        _native.filter_packed(packed, query)
        packed_s = time.perf_counter() - t0

        return {
            "bbox_f32_seconds": round(f32_s, 4),
            "bbox_f32_envelopes_per_sec": round(rows / f32_s),
            "bbox_f32_vs_numpy": round(np_s / f32_s, 1),
            "bbox_packed_seconds": round(packed_s, 4),
            "bbox_f32_vs_packed": round(packed_s / f32_s, 1),
            "bbox_rows": rows,
            "bbox_e2e_seconds": round(e2e_s, 4),
            "bbox_kernel_seconds": round(dev_s, 4),
            "bbox_envelopes_per_sec": round(rows / dev_s),
            "bbox_numpy_seconds": round(np_s, 4),
            "bbox_kernel_vs_numpy": round(np_s / dev_s, 1),
            "bbox_resident_repeat_seconds": round(resident_s, 4),
            "bbox_resident_beats_numpy": bool(resident_s < np_s),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"bbox bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}


def _fetch_resume_bench():
    """Fault-tolerant transport: kill an HTTP fetch mid-packstream
    (KART_FAULTS) and measure the resume — wall-clock of the retried
    fetch and how few objects it re-ships. The robustness analog of the
    throughput benchmarks: a dropped 100M-object clone must cost a
    remainder, not a restart. Returns {} on any failure."""
    import sys
    import tempfile
    import threading

    try:
        rows = int(os.environ.get("KART_BENCH_FETCH_ROWS", 50_000))
        if rows <= 0:
            return {}
        from kart_tpu.core.repo import KartRepo
        from kart_tpu.synth import synth_repo
        from kart_tpu.transport.http import HttpRemote, make_server
        from kart_tpu.transport.retry import RetryPolicy

        with tempfile.TemporaryDirectory() as td:
            repo, _ = synth_repo(
                os.path.join(td, "src"), rows, blobs="real", edit_frac=0.0
            )
            server = make_server(repo)
            threading.Thread(target=server.serve_forever, daemon=True).start()
            try:
                url = f"http://127.0.0.1:{server.server_address[1]}/"
                dst = KartRepo.init_repository(os.path.join(td, "dst"))
                client = HttpRemote(url, retry=RetryPolicy(attempts=1))
                info = client.ls_refs()
                wants = list(info["heads"].values())

                # kill the transfer halfway through the stream
                os.environ["KART_FAULTS"] = f"transport.read.frame:{rows // 2}"
                try:
                    client.fetch_pack(dst, wants)
                except Exception:  # kart: noqa(KTL006): the injected mid-stream kill IS the scenario; whatever shape it surfaces as, the salvage below is what's measured
                    pass
                finally:
                    os.environ.pop("KART_FAULTS", None)
                salvaged = set(dst.odb.iter_oids())

                t0 = time.perf_counter()
                header = client.fetch_pack(dst, wants, exclude=salvaged)
                resume_s = time.perf_counter() - t0
                resent = header["object_count"]
                total = len(salvaged) + resent
                assert sum(1 for _ in dst.odb.iter_oids()) == total
                return {
                    "fetch_resume_seconds": round(resume_s, 3),
                    "fetch_resume_objects_total": total,
                    "fetch_resume_objects_salvaged": len(salvaged),
                    "fetch_resume_objects_resent": resent,
                }
            finally:
                server.shutdown()
                server.server_close()
    except Exception as e:
        print(f"fetch-resume bench failed: {e}", file=sys.stderr)
        return {}


def _telemetry_overhead_bench():
    """The honesty check on the telemetry subsystem's "near-zero when
    disabled" claim: measure (1) the wall-clock of a 1M-row columnar diff
    classify with telemetry disabled, (2) how many telemetry calls that
    workload actually issues (counting stubs swapped in through the
    late-bound ``telemetry.span``/``telemetry.incr`` attributes — no call
    site changes), and (3) the per-call cost of the disabled no-op.
    ``telemetry_overhead_pct`` = calls x per-call / workload — computed
    rather than differenced because the no-op cost (~100ns x a handful of
    batch-level calls) is far below run-to-run timing noise on a
    multi-second workload. Returns {} on any failure."""
    import sys

    try:
        rows = int(os.environ.get("KART_BENCH_TELEMETRY_ROWS", 1_000_000))
        if rows <= 0:
            return {}
        from kart_tpu import telemetry
        from kart_tpu.diff.engine import get_feature_diff_columnar
        from kart_tpu.parallel.sharded_diff import synthetic_block

        old = synthetic_block(rows, seed=0)
        new = synthetic_block(rows, seed=0)
        new.oids = new.oids.copy()
        new.oids[7::100, 0] ^= 1  # 1% updates, as the headline config

        class _Ds:
            # value resolution stays lazy, so a promise stub is all the
            # delta loop touches
            path_encoder = None
            repo = None

            @staticmethod
            def get_feature_promise_from_oid(pks, oid):
                return None

        ds = _Ds()

        def workload():
            return get_feature_diff_columnar(ds, ds, blocks=(old, new))

        telemetry.reset()  # disabled: the production default
        workload()  # warm (jit/native load)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            workload()
            times.append(time.perf_counter() - t0)
        work_s = min(times)

        # count the telemetry calls the workload issues
        calls = [0]
        real_span, real_incr = telemetry.span, telemetry.incr

        def counting_span(name, **attrs):
            calls[0] += 1
            return real_span(name, **attrs)

        def counting_incr(name, n=1, **labels):
            calls[0] += 1
            return real_incr(name, n, **labels)

        telemetry.span, telemetry.incr = counting_span, counting_incr
        try:
            workload()
        finally:
            telemetry.span, telemetry.incr = real_span, real_incr
        n_calls = calls[0]

        # per-call cost of the disabled fast path (full enter/exit cycle)
        n_iter = 200_000
        t0 = time.perf_counter()
        for _ in range(n_iter):
            with telemetry.span("bench.noop"):
                pass
        span_s = (time.perf_counter() - t0) / n_iter
        t0 = time.perf_counter()
        for _ in range(n_iter):
            telemetry.incr("bench.noop")
        incr_s = (time.perf_counter() - t0) / n_iter
        per_call = max(span_s, incr_s)

        overhead_pct = (n_calls * per_call) / work_s * 100.0
        return {
            "telemetry_overhead_pct": round(overhead_pct, 4),
            "telemetry_noop_ns_per_call": round(per_call * 1e9, 1),
            "telemetry_calls_per_diff": n_calls,
            "telemetry_diff_rows": rows,
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"telemetry bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}


def _lint_bench():
    """ISSUE 4: the static-analysis suite's own cost — full-tree wall-clock
    and active-rule count. The <5s bound is tier-1 tested
    (tests/test_lint_clean.py); this records the measured number alongside
    the perf headlines so a rule that regresses the runtime shows up in the
    BENCH record. Returns {} on any failure."""
    import sys

    try:
        from kart_tpu import analysis
        from kart_tpu.analysis import dataflow

        t0 = time.perf_counter()
        report = analysis.run_lint()
        lint_s = time.perf_counter() - t0
        return {
            "lint_runtime_seconds": round(lint_s, 3),
            "lint_rules_total": len(report.rules),
            "lint_files_scanned": report.files_scanned,
            "lint_findings_total": len(report.findings),
            # ISSUE 11: the slowest single rule's wall-clock — keeps the
            # <5s bound attributable now that the rule count has doubled
            # (the interprocedural KTL010 family is the expected leader)
            "lint_rule_seconds_max": round(
                max(report.rule_seconds.values(), default=0.0), 3
            ),
            # ISSUE 19: taint-engine coverage — how many function bodies
            # the KTL030-034 dataflow pass analyzed (seeded sources plus
            # memoized callee passes); a drop means the wire surface
            # silently shrank
            "lint_taint_functions_analyzed": (
                dataflow.last_run_functions_analyzed()
            ),
        }
    except Exception as e:
        print(f"lint bench failed: {e}", file=sys.stderr)
        return {}


def _merge_bench():
    """BASELINE config #5: 3-way merge with 1M conflicting features — the
    vectorized classify kernel plus full conflict materialisation
    (label + AncestorOursTheirs objects). Returns {} on any failure."""
    import sys

    try:
        rows = int(os.environ.get("KART_BENCH_MERGE_ROWS", 1_000_000))
        if rows <= 0:
            return {}
        import numpy as np

        from kart_tpu.merge import materialise_conflicts
        from kart_tpu.diff.backend import merge_classify
        from kart_tpu.ops.merge_kernel import CONFLICT
        from kart_tpu.parallel.sharded_diff import synthetic_block

        from kart_tpu.models.paths import PathEncoder

        a = synthetic_block(rows, seed=0)
        o = synthetic_block(rows, seed=0)
        o.oids = o.oids.copy()
        o.oids[:, 0] ^= 1  # ours changed every row ...
        t = synthetic_block(rows, seed=0)
        t.oids = t.oids.copy()
        t.oids[:, 0] ^= 2  # ... theirs changed every row differently

        # real int-encoder paths + a dataset stub carrying the encoder, so
        # the measured labeling is the vectorized batch-decode path actual
        # int-pk datasets take
        encoder = PathEncoder.INT_PK_ENCODER
        paths = encoder.encode_paths_batch(np.arange(len(a.keys), dtype=np.int64))
        for b in (a, o, t):
            b.paths = paths

        class _Ds:
            path_encoder = encoder

            @staticmethod
            def decode_path_to_pks(rel):
                return encoder.decode_path_to_pks(rel)

        datasets = [_Ds(), _Ds(), _Ds()]

        merge_classify(a, o, t)  # warmup/compile
        t0 = time.perf_counter()
        union, decision, _, stats = merge_classify(a, o, t)
        classify_s = time.perf_counter() - t0
        assert stats["conflicts"] == rows, stats

        conflict_idx = np.nonzero(decision == CONFLICT)[0]
        t0 = time.perf_counter()
        conflicts = materialise_conflicts(
            "ds", [a, o, t], datasets, "inner", union, conflict_idx
        )
        materialise_s = time.perf_counter() - t0
        assert len(conflicts) == rows

        # the full persistence cost too: columnar KMIX1 stream-write + read
        import tempfile

        from kart_tpu.merge.index import MergeIndex

        mi = MergeIndex("0" * 40, conflicts)
        fd, idx_path = tempfile.mkstemp(prefix="kart-bench-kmix")
        try:
            # min of 2: serialisation cost, not transient disk-cache noise
            times = []
            for attempt in range(2):
                t0 = time.perf_counter()
                with (
                    os.fdopen(fd, "wb") if attempt == 0 else open(idx_path, "wb")
                ) as f:
                    for chunk in mi._binary_chunks():
                        f.write(chunk)
                times.append(time.perf_counter() - t0)
            index_write_s = min(times)
            t0 = time.perf_counter()
            with open(idx_path, "rb") as f:
                MergeIndex._from_binary(f.read())
            index_read_s = time.perf_counter() - t0
        finally:
            os.unlink(idx_path)

        total = classify_s + materialise_s
        return {
            "merge_conflict_rows": rows,
            "merge_classify_seconds": round(classify_s, 3),
            "merge_materialise_seconds": round(materialise_s, 3),
            "merge_index_write_seconds": round(index_write_s, 3),
            "merge_index_read_seconds": round(index_read_s, 3),
            "merge_conflicts_per_sec": round(rows / total),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"merge bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}


def _cli_diff_bench():
    """End-to-end `kart diff -o feature-count` wall-clock on a synthetic
    repo (default 1M rows, 1% edited): import -> edit-commit -> diff through
    the real CLI, routed over the columnar sidecar + device kernel, compared
    against the host tree-walk engine on the same repo.
    Returns {} on any failure — the headline kernel metric must still print."""
    import shutil
    import sys
    import tempfile

    work = None
    try:
        rows = int(os.environ.get("KART_BENCH_CLI_ROWS", 1_000_000))
        if rows <= 0:
            return {}
        work = tempfile.mkdtemp(prefix="kart-bench-")
        gpkg = os.path.join(work, "layer.gpkg")
        _build_bench_gpkg(gpkg, rows)

        from click.testing import CliRunner

        from kart_tpu.cli import cli

        runner = CliRunner()
        # import is disk/cache sensitive on this box (VERDICT r4 weak #3
        # recorded 15.4s for a path measured at ~9.8s in-round): run it 3x
        # into fresh repos and record min + median; the diff section uses
        # the last repo
        import_times = []
        cwd = os.getcwd()
        for i in range(3):
            repo_dir = os.path.join(work, f"repo{i}")
            r = runner.invoke(cli, ["init", repo_dir])
            assert r.exit_code == 0, r.output
            os.chdir(repo_dir)
            try:
                t0 = time.perf_counter()
                r = runner.invoke(cli, ["import", gpkg, "--no-checkout"])
                import_times.append(time.perf_counter() - t0)
            finally:
                os.chdir(cwd)
            assert r.exit_code == 0, r.output
            if i < 2:
                shutil.rmtree(repo_dir, ignore_errors=True)
        import_s = min(import_times)
        import_median_s = sorted(import_times)[len(import_times) // 2]
        os.chdir(repo_dir)
        try:
            _bench_edit_commit(rows)

            t0 = time.perf_counter()
            r = runner.invoke(
                cli, ["diff", "HEAD^...HEAD", "-o", "feature-count"]
            )
            assert r.exit_code == 0, r.output
            columnar_cold_s = time.perf_counter() - t0

            # steady state: compile amortised (persistent cache serves later
            # processes; within this one the jit cache is simply warm)
            t0 = time.perf_counter()
            r = runner.invoke(
                cli, ["diff", "HEAD^...HEAD", "-o", "feature-count"]
            )
            assert r.exit_code == 0, r.output
            columnar_s = time.perf_counter() - t0

            os.environ["KART_DIFF_ENGINE"] = "tree"
            try:
                t0 = time.perf_counter()
                r = runner.invoke(
                    cli, ["diff", "HEAD^...HEAD", "-o", "feature-count"]
                )
                assert r.exit_code == 0, r.output
                tree_s = time.perf_counter() - t0
            finally:
                os.environ.pop("KART_DIFF_ENGINE", None)
        finally:
            os.chdir(cwd)

        # import-leg phase breakdown (VERDICT r5 #6, measurement half): one
        # more import on the *serial* instrumented path — the parallel
        # fan-out interleaves phases across workers and the pipeline
        # overlaps them across threads, so the decomposition is taken
        # where each phase is separable (and its self-times provably sum
        # <= total); its own total makes the denominator explicit
        phases = {}
        serial_import_s = None
        phase_dir = os.path.join(work, "repo-phases")
        r = runner.invoke(cli, ["init", phase_dir])
        assert r.exit_code == 0, r.output
        os.environ["KART_IMPORT_WORKERS"] = "1"
        os.environ["KART_IMPORT_PIPELINE"] = "0"
        os.chdir(phase_dir)
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, ["import", gpkg, "--no-checkout"])
            serial_import_s = time.perf_counter() - t0
        finally:
            os.chdir(cwd)
            os.environ.pop("KART_IMPORT_WORKERS", None)
            os.environ.pop("KART_IMPORT_PIPELINE", None)
        assert r.exit_code == 0, r.output
        from kart_tpu.importer.importer import LAST_IMPORT_PHASES

        if LAST_IMPORT_PHASES:
            p = LAST_IMPORT_PHASES
            phases = {
                "import_phase_source_read_seconds": round(p["source_read"], 3),
                "import_phase_encode_seconds": round(p["encode"], 3),
                "import_phase_hash_deflate_seconds": round(p["hash_deflate"], 3),
                "import_phase_tree_build_seconds": round(p["tree_build"], 3),
                "import_serial_seconds": round(serial_import_s, 3),
            }
        shutil.rmtree(phase_dir, ignore_errors=True)

        # pipelined leg (ISSUE 5): the same import through the bounded
        # 4-stage pipeline on one process (workers=1 keeps the parallel
        # fan-out from preempting it) — the speedup over the serial
        # instrumented leg above is the overlap actually won
        pipe_dir = os.path.join(work, "repo-pipeline")
        r = runner.invoke(cli, ["init", pipe_dir])
        assert r.exit_code == 0, r.output
        os.environ["KART_IMPORT_WORKERS"] = "1"
        os.environ["KART_IMPORT_PIPELINE"] = "1"
        os.chdir(pipe_dir)
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, ["import", gpkg, "--no-checkout"])
            pipeline_import_s = time.perf_counter() - t0
        finally:
            os.chdir(cwd)
            os.environ.pop("KART_IMPORT_WORKERS", None)
            os.environ.pop("KART_IMPORT_PIPELINE", None)
        assert r.exit_code == 0, r.output
        if serial_import_s is not None:
            phases["import_pipeline_seconds"] = round(pipeline_import_s, 3)
            phases["import_pipeline_speedup"] = round(
                serial_import_s / pipeline_import_s, 2
            )
        shutil.rmtree(pipe_dir, ignore_errors=True)

        # working-copy checkout / incremental reset (VERDICT r5 #7): GPKG
        # write_full of the full layer through the CLI, the incremental
        # reset via the library (the CLI reset forces a full rewrite), and
        # a same-machine reference-loop comparison
        os.chdir(repo_dir)
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, ["checkout"])
            assert r.exit_code == 0, r.output
            wc_checkout_s = time.perf_counter() - t0

            from kart_tpu.core.repo import KartRepo

            repo = KartRepo(".")
            wc = repo.working_copy
            t0 = time.perf_counter()
            wc.reset(repo.structure("HEAD^"))  # incremental: 1% of rows
            wc_reset_s = time.perf_counter() - t0
            ref_wc_rate = _reference_checkout_rate(repo)
        finally:
            os.chdir(cwd)

        return {
            "cli_diff_rows": rows,
            "cli_import_seconds": round(import_s, 3),
            "cli_import_seconds_median": round(import_median_s, 3),
            "import_features_per_sec": round(rows / import_s),
            **phases,
            "cli_diff_columnar_cold_seconds": round(columnar_cold_s, 3),
            "cli_diff_columnar_seconds": round(columnar_s, 3),
            "cli_diff_tree_seconds": round(tree_s, 3),
            "cli_diff_rows_per_sec": round(rows / columnar_s),
            "wc_checkout_seconds": round(wc_checkout_s, 2),
            "wc_checkout_features_per_sec": round(rows / wc_checkout_s),
            "wc_reset_seconds": round(wc_reset_s, 3),
            "reference_checkout_rate": round(ref_wc_rate),
            "wc_checkout_vs_reference": round(rows / wc_checkout_s / ref_wc_rate, 1),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"cli bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)


def _import_10m_bench():
    """10M-row end-to-end `kart import` (ISSUE 5): the 100M extrapolation
    was previously a guess from the 1M leg; this leg measures a real
    10M-feature source through whatever path the routing heuristics pick
    (parallel fan-out on big boxes, the pipeline otherwise).
    KART_BENCH_10M_IMPORT_ROWS=0 disables. Returns {} on any failure."""
    import shutil
    import sys
    import tempfile

    work = None
    try:
        rows = int(os.environ.get("KART_BENCH_10M_IMPORT_ROWS", 10_000_000))
        if rows <= 0:
            return {}
        work = tempfile.mkdtemp(prefix="kart-bench-10m-")
        gpkg = os.path.join(work, "layer.gpkg")
        _build_bench_gpkg(gpkg, rows)

        from click.testing import CliRunner

        from kart_tpu.cli import cli

        runner = CliRunner()
        repo_dir = os.path.join(work, "repo")
        r = runner.invoke(cli, ["init", repo_dir])
        assert r.exit_code == 0, r.output
        cwd = os.getcwd()
        os.chdir(repo_dir)
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, ["import", gpkg, "--no-checkout"])
            import_s = time.perf_counter() - t0
        finally:
            os.chdir(cwd)
        assert r.exit_code == 0, r.output
        return {
            "cli_10m_import_rows": rows,
            "cli_10m_import_seconds": round(import_s, 3),
            "import_features_per_sec_10m": round(rows / import_s),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"10m import bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)


def _estimation_bench():
    """Sampled diff estimation (SURVEY §2.3 sampled reduction; the r3
    device-sharded estimation feature): estimate vs exact on a 10M-row
    block pair, timed. Returns {} on any failure."""
    import sys

    try:
        rows = int(os.environ.get("KART_BENCH_EST_ROWS", 10_000_000))
        if rows <= 0:
            return {}
        import numpy as np

        from kart_tpu.diff.estimation import estimate_counts_from_blocks
        from kart_tpu.parallel.sharded_diff import synthetic_block

        old = synthetic_block(rows, seed=3)
        new = synthetic_block(rows, seed=3)
        new.oids = new.oids.copy()
        idx = np.arange(11, rows, 100)
        new.oids[idx, 0] ^= 1
        exact = len(idx)

        estimate_counts_from_blocks(old, new, "medium")  # warm/compile
        t0 = time.perf_counter()
        est = estimate_counts_from_blocks(old, new, "medium")
        est_s = time.perf_counter() - t0
        err_pct = abs(est - exact) / exact * 100.0
        return {
            "estimation_rows": rows,
            "estimation_seconds": round(est_s, 3),
            "estimation_error_pct": round(err_pct, 2),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"estimation bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}


def _cli_polygon_diff():
    """BASELINE config #3: 10M-row polygon layer diff with real blobs,
    measured through `kart diff -o json-lines --output <file>` so the full
    value-materialisation path is timed — batch pack reads + inflate, path
    decode, WKB->hex geometry output, JSON writing (the reference's
    equivalent loop: base_diff_writer.py:279-341). Every changed feature's
    old AND new value is materialised. KART_BENCH_POLY_ROWS=0 disables."""
    import shutil
    import sys
    import tempfile

    work = None
    try:
        rows = int(os.environ.get("KART_BENCH_POLY_ROWS", 10_000_000))
        if rows <= 0:
            return {}
        work = tempfile.mkdtemp(prefix="kart-bench-poly-")
        from kart_tpu.synth import synth_polygon_repo

        t0 = time.perf_counter()
        _, info = synth_polygon_repo(
            os.path.join(work, "repo"), rows, edit_frac=0.01
        )
        synth_s = time.perf_counter() - t0

        from click.testing import CliRunner

        from kart_tpu.cli import cli

        sink = os.path.join(work, "out.jsonl")
        args = [
            "-C", os.path.join(work, "repo"), "diff", "HEAD^...HEAD",
            "-o", "json-lines", "--output", sink,
        ]
        runner = CliRunner()
        t0 = time.perf_counter()
        r = runner.invoke(cli, args)
        assert r.exit_code == 0, r.output
        cold_s = time.perf_counter() - t0
        # min of 2 warm runs: the section runs late in the bench and a
        # single warm sample inherits cache pressure from earlier sections
        warm_times = []
        for _ in range(2):
            t0 = time.perf_counter()
            r = runner.invoke(cli, args)
            assert r.exit_code == 0, r.output
            warm_times.append(time.perf_counter() - t0)
        warm_s = min(warm_times)
        # updates materialise old + new values
        n_materialised = 2 * info["n_edits"]
        with open(sink) as f:
            n_lines = sum(1 for _ in f)
        assert n_lines >= info["n_edits"], (n_lines, info)
        ref_rate = _reference_materialise_rate(os.path.join(work, "repo"))
        return {
            "poly_rows": rows,
            "poly_synth_seconds": round(synth_s, 1),
            "cli_10m_polygon_diff_cold_seconds": round(cold_s, 2),
            "cli_10m_polygon_diff_seconds": round(warm_s, 2),
            "features_materialised_per_sec": round(n_materialised / warm_s),
            "reference_materialise_rate": round(ref_rate),
            "materialise_vs_reference": round(n_materialised / warm_s / ref_rate, 1),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"polygon bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)


def _reference_materialise_rate(repo_path, slice_n=4000):
    """Features/s of the reference's value-materialisation loop
    (kart/base_diff_writer.py:279-341 + dataset3.py:185-223) re-created
    over our storage: per changed feature, a single-object odb read (pack
    bisect + one-shot inflate), msgpack decode, legend zip into a dict,
    geometry->hexWKB conversion, and a json.dumps per line — no batch
    prefetch, no fused decode. Measured on a slice of the diff and
    reported as a rate (the loop is O(changed))."""
    import io as _io
    import json as _json

    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff.engine import get_dataset_diff
    from kart_tpu.diff.output import feature_as_json

    repo = KartRepo(repo_path)
    base_rs = repo.structure("HEAD^")
    target_rs = repo.structure("HEAD")
    ds_path = base_rs.datasets.paths()[0]
    ds_diff = get_dataset_diff(base_rs, target_rs, ds_path)
    items = list(itertools.islice(ds_diff["feature"].sorted_items(), slice_n))
    sink = _io.StringIO()
    n = 0
    t0 = time.perf_counter()
    for _key, delta in items:
        change = {}
        if delta.old:
            change["-"] = feature_as_json(delta.old_value, delta.old_key)
            n += 1
        if delta.new:
            change["+"] = feature_as_json(delta.new_value, delta.new_key)
            n += 1
        sink.write(_json.dumps({"type": "feature", "change": change}))
        sink.write("\n")
    dt = time.perf_counter() - t0
    return n / dt


def _reference_checkout_rate(repo, slice_n=50_000):
    """Features/s of the reference's working-copy checkout loop
    (kart/working_copy/base.py write_full) re-created over our storage:
    per feature, a single-object odb read (pack bisect + one-shot inflate,
    no batch prefetch), a name-keyed dict build, per-cell GPKG value
    conversion, and executemany batches of 1000 into sqlite. Measured on a
    slice and reported as a rate (the loop is O(n))."""
    import sqlite3

    from kart_tpu.adapters import gpkg as gpkg_adapter

    structure = repo.structure("HEAD")
    ds = structure.datasets[structure.datasets.paths()[0]]
    schema = ds.schema
    feature_tree = ds.feature_tree
    odb = feature_tree.odb
    entries = []
    for path, entry in feature_tree.walk_blobs():
        entries.append((path, entry.oid))
        if len(entries) >= slice_n:
            break

    con = sqlite3.connect(":memory:")
    cols = ",".join(f'"{c.name}"' for c in schema.columns)
    qs = ",".join("?" for _ in schema.columns)
    con.execute(
        "CREATE TABLE t (" + ",".join(f'"{c.name}"' for c in schema.columns) + ")"
    )
    insert_sql = f"INSERT INTO t ({cols}) VALUES ({qs})"
    t0 = time.perf_counter()
    batch = []
    for path, oid in entries:
        data = odb.read_blob(oid)  # single-object read, as the reference
        feature = ds.get_feature(ds.decode_path_to_pks(path), data=data)
        batch.append(
            tuple(
                gpkg_adapter.value_from_v2(feature[c.name], c, crs_id=4326)
                for c in schema.columns
            )
        )
        if len(batch) >= 1000:
            con.executemany(insert_sql, batch)
            batch.clear()
    if batch:
        con.executemany(insert_sql, batch)
    dt = time.perf_counter() - t0
    con.close()
    return len(entries) / dt


def _cli_diff_100m():
    """The north-star number (BASELINE.json): end-to-end `kart diff -o
    feature-count` on a 100M-feature layer, < 60 s target. The repo is
    synthesized directly (kart_tpu/synth.py: real Merkle feature trees +
    sidecars, blobs promised — the partial-clone state; tree oids are
    bit-identical to a real import, tested in tests/test_synth.py), then the
    diff runs through the exact production CLI path. Recorded twice: with
    normal engine routing (device when it wins) and with the host engine
    forced, because host<->HBM transfer can dominate and routing
    legitimately differs per deployment.
    KART_BENCH_100M_ROWS=0 disables."""
    import shutil
    import sys
    import tempfile

    work = None
    try:
        rows = int(os.environ.get("KART_BENCH_100M_ROWS", 100_000_000))
        if rows <= 0:
            return {}
        work = tempfile.mkdtemp(prefix="kart-bench-100m-")
        from kart_tpu.synth import synth_repo

        t0 = time.perf_counter()
        # blobs="changed": the ~1M edited rows carry real blobs in both
        # revisions — exactly the set the full-output diff materialises —
        # while the other 99M stay promised (partial-clone state)
        repo, _info = synth_repo(
            os.path.join(work, "repo"), rows, edit_frac=0.01,
            blobs="changed", spatial=True,
        )
        synth_s = time.perf_counter() - t0

        from click.testing import CliRunner

        from kart_tpu.cli import cli

        runner = CliRunner()
        args = ["-C", os.path.join(work, "repo"), "diff", "HEAD^...HEAD", "-o", "feature-count"]

        t0 = time.perf_counter()
        r = runner.invoke(cli, args)
        assert r.exit_code == 0, r.output
        routed_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = runner.invoke(cli, args)
        assert r.exit_code == 0, r.output
        routed_s = time.perf_counter() - t0

        # host engine: every device route closed (no device round trip)
        os.environ["KART_DIFF_BACKEND"] = "host_native"
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, args)
            assert r.exit_code == 0, r.output
            host_s = time.perf_counter() - t0
        finally:
            os.environ.pop("KART_DIFF_BACKEND", None)

        # BASELINE config #4: the spatially-filtered diff through the same
        # CLI — envelope-column batch lookup, bbox prefilter kernel,
        # classify on the surviving subset (it scans less, so it must beat
        # the unfiltered number)
        from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

        # a region-sized filter (~1% of the globe — the reference's spatial
        # filters are city/region extracts, not hemispheres)
        spec = ResolvedSpatialFilterSpec.from_spec_string(
            "EPSG:4326;POLYGON((-40 -20, -4 -20, -4 -3, -40 -3, -40 -20))"
        )
        repo.config.set_many(spec.config_items())
        t0 = time.perf_counter()
        r = runner.invoke(cli, args)
        assert r.exit_code == 0, r.output
        spatial_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = runner.invoke(cli, args)
        assert r.exit_code == 0, r.output
        spatial_s = time.perf_counter() - t0
        spatial_out = r.output
        # the same filtered diff with block pruning disabled (the r5-style
        # full envelope scan), proving the pruning wins AND that the output
        # is identical (the acceptance pair for the block-aggregate change)
        os.environ["KART_BLOCK_PRUNE"] = "0"
        try:
            t0 = time.perf_counter()
            r = runner.invoke(cli, args)
            assert r.exit_code == 0, r.output
            spatial_unpruned_s = time.perf_counter() - t0
            spatial_unpruned_out = r.output
        finally:
            os.environ.pop("KART_BLOCK_PRUNE", None)
        for key in spec.config_items():
            repo.del_config(key)

        # full-output json-lines diff over the ~1M-row changed set: the
        # fused materialisation pipeline (batch pack-read -> inflate ->
        # msgpack-decode -> compiled serialise), end to end through the CLI
        sink = os.path.join(work, "fulldiff.jsonl")
        full_args = [
            "-C", os.path.join(work, "repo"), "diff", "HEAD^...HEAD",
            "-o", "json-lines", "--output", sink,
        ]
        t0 = time.perf_counter()
        r = runner.invoke(cli, full_args)
        assert r.exit_code == 0, r.output
        fulldiff_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = runner.invoke(cli, full_args)
        assert r.exit_code == 0, r.output
        fulldiff_s = time.perf_counter() - t0
        with open(sink) as f:
            n_lines = sum(1 for _ in f)
        n_edits = _info["n_edits"]
        assert n_lines >= n_edits, (n_lines, n_edits)
        n_materialised = 2 * n_edits  # updates materialise old + new

        # the north-star flag is the ROUTED production path, nothing else
        # (VERDICT r3 weak #2: a forced-host number must never wear this
        # label); the host-engine time stays recorded for engine comparison
        return {
            "cli_100m_rows": rows,
            "cli_100m_synth_seconds": round(synth_s, 1),
            "cli_100m_diff_cold_seconds": round(routed_cold_s, 2),
            "cli_100m_diff_seconds": round(routed_s, 2),
            "cli_100m_diff_host_engine_seconds": round(host_s, 2),
            "cli_100m_spatial_diff_cold_seconds": round(spatial_cold_s, 2),
            "cli_100m_spatial_diff_seconds": round(spatial_s, 2),
            "cli_100m_spatial_unpruned_seconds": round(spatial_unpruned_s, 2),
            "cli_100m_spatial_output_matches_unpruned": bool(
                spatial_out == spatial_unpruned_out
            ),
            # the filtered diff answers a strictly harder question (which
            # deltas match the filter); with block-pruned aggregates the
            # envelope pass touches only boundary blocks, so it must now
            # undercut the unfiltered scan (ISSUE 1 acceptance), and the
            # r4 bar stays recorded for continuity
            "cli_100m_spatial_beats_unfiltered": bool(spatial_s < routed_s),
            "cli_100m_spatial_beats_r4_bar": bool(
                rows < 100_000_000 or spatial_s < 4.31
            ),
            "cli_100m_fulldiff_cold_seconds": round(fulldiff_cold_s, 2),
            "cli_100m_fulldiff_seconds": round(fulldiff_s, 2),
            "cli_100m_fulldiff_rows_materialised": n_materialised,
            # the headline materialisation rate, at the 1M-changed scale
            # (supersedes the 10M-polygon section's smaller-sample number
            # printed in the interim record)
            "features_materialised_per_sec": round(n_materialised / fulldiff_s),
            "cli_100m_north_star_met": bool(routed_s < 60.0),
        }
    except Exception as e:  # pragma: no cover - bench resilience
        print(f"100m bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        return {}
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)


def _build_bench_gpkg(path, rows):
    import sqlite3
    import struct

    con = sqlite3.connect(path)
    con.executescript(
        """
        PRAGMA journal_mode=OFF; PRAGMA synchronous=OFF;
        CREATE TABLE gpkg_contents (
            table_name TEXT NOT NULL PRIMARY KEY, data_type TEXT NOT NULL,
            identifier TEXT UNIQUE, description TEXT DEFAULT '',
            last_change DATETIME, min_x DOUBLE, min_y DOUBLE,
            max_x DOUBLE, max_y DOUBLE, srs_id INTEGER);
        CREATE TABLE gpkg_geometry_columns (
            table_name TEXT NOT NULL, column_name TEXT NOT NULL,
            geometry_type_name TEXT NOT NULL, srs_id INTEGER NOT NULL,
            z TINYINT NOT NULL, m TINYINT NOT NULL);
        CREATE TABLE gpkg_spatial_ref_sys (
            srs_name TEXT NOT NULL, srs_id INTEGER NOT NULL PRIMARY KEY,
            organization TEXT NOT NULL, organization_coordsys_id INTEGER NOT NULL,
            definition TEXT NOT NULL, description TEXT);
        CREATE TABLE layer (
            fid INTEGER PRIMARY KEY NOT NULL,
            geom POINT, name TEXT, value REAL);
        """
    )
    from kart_tpu.crs import WGS84_WKT

    con.execute(
        "INSERT INTO gpkg_spatial_ref_sys VALUES "
        "('WGS 84', 4326, 'EPSG', 4326, ?, NULL)",
        (WGS84_WKT,),
    )
    con.execute(
        "INSERT INTO gpkg_contents (table_name, data_type, identifier, srs_id) "
        "VALUES ('layer', 'features', 'bench layer', 4326)"
    )
    con.execute(
        "INSERT INTO gpkg_geometry_columns VALUES ('layer', 'geom', 'POINT', 4326, 0, 0)"
    )
    header = b"GP\x00\x01" + struct.pack("<i", 4326)

    def gen():
        for i in range(1, rows + 1):
            x = (i % 360) - 180 + 0.001
            y = (i % 170) - 85 + 0.001
            geom = header + struct.pack("<BI2d", 1, 1, x, y)
            yield (i, geom, f"feature-{i}", i / 3.0)

    con.executemany("INSERT INTO layer VALUES (?, ?, ?, ?)", gen())
    con.commit()
    con.close()


def _bench_edit_commit(rows):
    """Commit an update to 1% of features (every 100th row) via the library
    API (the WC round-trip isn't what this benchmark measures)."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.diff.structs import (
        DatasetDiff,
        Delta,
        DeltaDiff,
        KeyValue,
        RepoDiff,
    )

    repo = KartRepo(".")
    repo.config.set_many({"user.name": "bench", "user.email": "b@example.com"})
    structure = repo.structure("HEAD")
    ds = structure.datasets["layer"]
    feature_diff = DeltaDiff()
    for pk in range(7, rows, 100):
        old = ds.get_feature([pk])
        new = {**old, "value": old["value"] + 1.0}
        feature_diff.add_delta(
            Delta.update(KeyValue((pk, old)), KeyValue((pk, new)))
        )
    ds_diff = DatasetDiff()
    ds_diff["feature"] = feature_diff
    repo_diff = RepoDiff()
    repo_diff["layer"] = ds_diff
    structure.commit_diff(repo_diff, "bench edit", validate=False)


# --- multichip scaling bench (ISSUE 6) --------------------------------------
#
# `python bench.py --multichip` measures the 100M-row classify through the
# sharded backend's record-batch path at 1/2/4/8 devices and prints one JSON
# record (MULTICHIP_r*.json). Devices are *worker processes*, one pinned core
# each: on real multi-chip hosts each worker owns a chip; on a CPU-only
# container they are virtual devices, so the curve measures honest per-core
# scaling (the 1-dev leg is pinned to one core too — no hidden intra-op
# threads inflating the baseline). The mesh is as fast as its stragglers, so
# the aggregate rate divides total rows by the *slowest* shard's wall time,
# and all shards start together (a stdin go-barrier after every worker has
# compiled and generated its slice). The record embeds measured environment
# ceilings — pure-ALU and memcpy 2-process scaling — so a core-starved or
# bandwidth-starved container's flat tail reads as what it is.


def _multichip_slice(lo, hi):
    """(old_block, new_block) for global key range [lo, hi) of the synthetic
    100M pair: keys are the range itself, oids derive from the key (splitmix
    constant), 1 row in CHANGE_STRIDE gets edited oids — any shard of the
    key space is generable locally, nothing crosses process boundaries."""
    from kart_tpu.ops.blocks import FeatureBlock

    keys = np.arange(lo, hi, dtype=np.int64)
    h = keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    oids = np.empty((len(keys), 5), dtype=np.uint32)
    for i in range(5):
        oids[:, i] = ((h >> np.uint64(i * 12)) & np.uint64(0xFFFFFFFF)).astype(
            np.uint32
        )
    new_oids = oids.copy()
    changed = (keys % CHANGE_STRIDE) == 7
    new_oids[changed, 0] ^= 1
    n = len(keys)
    return (
        FeatureBlock(keys, oids, None, n),
        FeatureBlock(keys.copy(), new_oids, None, n),
    )


def multichip_worker():
    """One device of the multichip bench: pin to a core, insulate onto a
    1-device platform, compile + generate, report ready, block on the
    go-barrier, then classify the whole slice once against the clock.

    argv: --multichip-worker <mode> <lo> <hi> <cpu>; ``mode`` is
    ``batched`` (the sharded backend's record-batch loader — every shard of
    the 2/4/8-device legs) or ``mono`` (the monolithic single-device jitted
    kernel, exactly what ``device_jax`` executes on one chip — the 1-device
    leg). Prints two JSON lines (ready, result)."""
    import sys

    args = sys.argv[sys.argv.index("--multichip-worker") + 1 :]
    mode, lo, hi, cpu = args[0], int(args[1]), int(args[2]), int(args[3])
    try:
        os.sched_setaffinity(0, {cpu})
    except (AttributeError, OSError):
        pass  # non-Linux: unpinned workers still measure, just noisier

    from kart_tpu.runtime import insulate_virtual_cpu, probe_backend

    insulate_virtual_cpu(1)  # CPU on purpose: see multichip_main
    info = probe_backend()
    if not info["ok"]:
        print(json.dumps({"ready": False, "error": info["error"]}), flush=True)
        sys.exit(3)

    old_block, new_block = _multichip_slice(lo, hi)
    if mode == "mono":
        from kart_tpu.ops.blocks import PAD_KEY, bucket_size
        from kart_tpu.ops.diff_kernel import _classify_padded

        def padded(block):
            size = bucket_size(max(block.count, 1))
            keys = np.full(size, PAD_KEY, dtype=np.int64)
            keys[: block.count] = block.keys
            oids = np.zeros((size, 5), dtype=np.uint32)
            oids[: block.count] = block.oids
            return keys, oids

        sides = padded(old_block) + padded(new_block)

        # compile + first-touch at full shape (jit specialises per padded
        # bucket size, so a tiny warm pair would not pre-pay this compile)
        def run():
            oc, ncl, _, cnt = _classify_padded(
                *sides, old_block.count, new_block.count
            )
            cnt = np.asarray(cnt)
            # worker-protocol counts (same shape as the classify counts
            # dict), not a bench-record section
            return dict(
                zip(("inserts", "updates", "deletes"), (int(c) for c in cnt))
            )

        run()
    else:
        from kart_tpu.diff.device_batch import classify_blocks_batched
        from kart_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(1)
        # compile with the production batch shape before the clock starts: a
        # tiny warm pair hits the same (S, B) fixed shapes as the real slice
        warm_old, warm_new = _multichip_slice(0, 4096)
        classify_blocks_batched(warm_old, warm_new, mesh=mesh)

        def run():
            return classify_blocks_batched(old_block, new_block, mesh=mesh)[2]

    print(
        json.dumps({"ready": True, "probe_cached": bool(info.get("cached"))}),
        flush=True,
    )
    sys.stdin.readline()  # go-barrier: all shards start together
    t0 = time.perf_counter()
    counts = run()
    elapsed = time.perf_counter() - t0
    print(json.dumps({"seconds": elapsed, "rows": hi - lo, "counts": counts}), flush=True)


def _multichip_leg(n, n_dev, timeout_s, mode="batched"):
    """-> (rows/s aggregate over the slowest shard, all-probes-cached flag,
    counts-exact flag) for one device count, or (0, False, False) on any
    worker failure/timeout."""
    import subprocess
    import sys

    import select

    cpus = (
        sorted(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") else [0]
    )
    bounds = [n * i // n_dev for i in range(n_dev + 1)]
    deadline = time.monotonic() + timeout_s
    procs = []

    def read_line_bounded(p):
        """One worker line, or None at the leg deadline — a worker wedged
        in compile/generate must not hang the bench past its watchdog."""
        r, _, _ = select.select(
            [p.stdout], [], [], max(deadline - time.monotonic(), 0)
        )
        return p.stdout.readline() if r else None

    try:
        for s in range(n_dev):
            p = subprocess.Popen(
                [
                    sys.executable,
                    os.path.abspath(__file__),
                    "--multichip-worker",
                    mode,
                    str(bounds[s]),
                    str(bounds[s + 1]),
                    str(cpus[s % len(cpus)]),
                ],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                text=True,
            )
            procs.append(p)
        ready = [json.loads(read_line_bounded(p) or "{}") for p in procs]
        if not all(r.get("ready") for r in ready):
            return 0, False, False
        for p in procs:  # the barrier: every shard compiled + generated
            p.stdin.write("go\n")
            p.stdin.flush()
        results = []
        for p in procs:
            p.wait(timeout=max(deadline - time.monotonic(), 1))
            results.append(json.loads(p.stdout.readline() or "{}"))
    except (subprocess.TimeoutExpired, ValueError, OSError):
        return 0, False, False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
            for stream in (p.stdin, p.stdout):
                if stream:
                    stream.close()
    if not all("seconds" in r for r in results):
        return 0, False, False
    slowest = max(r["seconds"] for r in results)
    updates = sum(r["counts"]["updates"] for r in results)
    others = sum(r["counts"]["inserts"] + r["counts"]["deletes"] for r in results)
    want_updates = len(range(7, n, CHANGE_STRIDE))
    counts_exact = updates == want_updates and others == 0
    cached = all(r.get("probe_cached") for r in ready)
    return n / slowest, cached, counts_exact


def _env_2proc_scaling(task_src, cpus):
    """Measured environment ceiling: aggregate speedup of running ``task_src``
    as 2 concurrent pinned processes vs 1 (2.0 = perfect, ~1.0 = the
    resource is already saturated by one process)."""
    import subprocess
    import sys

    def run(cpu_list):
        procs = []
        for cpu in cpu_list:
            p = subprocess.Popen(
                [sys.executable, "-c", task_src % cpu],
                stdout=subprocess.PIPE,
                text=True,
            )
            procs.append(p)
        times = []
        try:
            for p in procs:
                p.wait(timeout=120)
                times.append(float(p.stdout.read().strip()))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
                if p.stdout:
                    p.stdout.close()
        return max(times)

    t1 = run(cpus[:1])
    t2 = run((cpus * 2)[:2])
    return round(2 * t1 / t2, 2) if t2 else 0.0


_ALU_TASK = """
import os, time
try: os.sched_setaffinity(0, {%d})
except Exception: pass
import numpy as np
a = np.arange(2_000_000, dtype=np.uint64)
t0 = time.perf_counter()
for _ in range(60):
    a = a * np.uint64(2654435761) + np.uint64(12345)
print(time.perf_counter() - t0)
"""

_MEMCPY_TASK = """
import os, time
try: os.sched_setaffinity(0, {%d})
except Exception: pass
import numpy as np
a = np.random.default_rng(0).integers(0, 255, size=200_000_000, dtype=np.uint8)
b = np.empty_like(a)
t0 = time.perf_counter()
for _ in range(10):
    np.copyto(b, a)
print(time.perf_counter() - t0)
"""


def multichip_main():
    """Whole multichip bench: probe-verdict prewarm, the 1/2/4/8-device
    scaling sweep, environment ceilings. Prints exactly one JSON record."""
    import subprocess
    import sys
    import tempfile

    n = int(os.environ.get("KART_BENCH_MULTICHIP_ROWS", 100_000_000))
    timeout_s = int(os.environ.get("KART_BENCH_TIMEOUT", 2400))

    cache = tempfile.NamedTemporaryFile(
        prefix="kart_probe_", suffix=".json", delete=False
    )
    cache.close()
    os.unlink(cache.name)
    # the legs are CPU-pinned on purpose: this mode measures the sharded
    # backend's scaling over virtual CPU devices, one process per device,
    # and a chip belongs to one process at a time — N workers could not
    # share it. Set in os.environ itself, not a copy: the leg workers are
    # spawned with the inherited environment.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["KART_PROBE_CACHE"] = cache.name
    env = dict(os.environ)
    # prewarm: one throwaway process pays the probe so every bench worker
    # adopts the *persisted* verdict (the "cached choice, not a re-paid
    # timeout" claim, measured rather than asserted)
    prewarm = subprocess.run(
        [
            sys.executable,
            "-c",
            "from kart_tpu.runtime import insulate_virtual_cpu, probe_backend;"
            "insulate_virtual_cpu(1); import sys;"
            "sys.exit(0 if probe_backend()['ok'] else 3)",
        ],
        env=env,
        timeout=600,
    )

    cpus = (
        sorted(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") else [0]
    )
    # the 1-device leg runs what one device actually executes — the
    # monolithic single-device jitted kernel (device_jax); the multi-device
    # legs run what a mesh actually executes — the sharded record-batch
    # loader (sharded_jax). The 1→2 step therefore contains both the
    # fixed-shape-batching win and the parallel speedup; the batched-1dev
    # key + the env ceilings below decompose the two honestly.
    legs = [
        (1, "mono", "multichip_classify_rows_per_sec_1dev"),
        (1, "batched", "multichip_classify_rows_per_sec_1dev_batched"),
        (2, "batched", "multichip_classify_rows_per_sec_2dev"),
        (4, "batched", "multichip_classify_rows_per_sec_4dev"),
        (8, "batched", "multichip_classify_rows_per_sec_8dev"),
    ]
    record = {
        "n_devices": 8,
        "ok": prewarm.returncode == 0,
        "skipped": False,
        "multichip_rows": n,
        "multichip_kernel": "sort",
        "multichip_host_cores": len(cpus),
        "backend_probe_cached": 0,
        "multichip_counts_exact": 1,
    }
    cached_all, exact_all = True, True
    rates = {}
    for n_dev, mode, key in legs:
        rate, cached, exact = _multichip_leg(n, n_dev, timeout_s, mode)
        rates[(n_dev, mode)] = rate
        record[key] = round(rate)
        cached_all &= cached
        exact_all &= exact
        record["backend_probe_cached"] = int(cached_all)
        record["multichip_counts_exact"] = int(exact_all)
        record["ok"] = record["ok"] and rate > 0
        print(json.dumps(record), flush=True)  # salvage partial sweeps
    if rates.get((1, "mono")):
        one = rates[(1, "mono")]
        record["multichip_scaling_1to2"] = round(rates[(2, "batched")] / one, 2)
        record["multichip_scaling_1to4"] = round(rates[(4, "batched")] / one, 2)
    record["multichip_env_alu_2proc_scaling"] = _env_2proc_scaling(_ALU_TASK, cpus)
    record["multichip_env_memcpy_2proc_scaling"] = _env_2proc_scaling(
        _MEMCPY_TASK, cpus
    )
    try:
        os.unlink(cache.name)
    except FileNotFoundError:
        pass  # prewarm died before persisting a verdict; nothing to clean
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# --serve-storm: N concurrent clients vs one kart serve (ISSUE 7)
# ---------------------------------------------------------------------------


def _storm_env(extra=None):
    """Environment for spawned servers/workers: this repo importable, no
    inherited fault arming, and pinned to the CPU platform — a chip belongs
    to one process at a time, so a server plus N client processes could
    not all hold it (the storm modes time the host-side serving path)."""
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("KART_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    if extra:
        env.update(extra)
    return env


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_serve(workdir, port, extra_env=None):
    """-> a `kart serve` subprocess accepting on 127.0.0.1:port."""
    import socket
    import subprocess
    import sys

    def _prioritise():
        # under a storm the single server process contends with N client
        # processes for the same cores; fair scheduling would starve it to
        # 1/(N+1) of a core and make *it* the bottleneck. Prioritising the
        # serving process is standard deployment practice; best-effort.
        try:
            os.nice(-10)
        except OSError as e:
            print(f"serve nice failed: {e}", file=sys.stderr)

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "kart_tpu.cli", "serve",
            "--host", "127.0.0.1", "--port", str(port),
        ],
        cwd=workdir,
        env=_storm_env(extra_env),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        preexec_fn=_prioritise,
    )
    deadline = time.monotonic() + 60
    while True:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return proc
        except OSError:
            if proc.poll() is not None or time.monotonic() > deadline:
                proc.kill()
                proc.wait()
                raise RuntimeError("kart serve did not start for the storm bench")
            time.sleep(0.1)


def _spawn_storm_workers(url, base, n_workers, n_requests, mode):
    import subprocess
    import sys

    procs = []
    try:
        for i in range(n_workers):
            p = subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--serve-storm-worker", url,
                    os.path.join(base, f"w{i}"), str(n_requests), mode,
                ],
                env=_storm_env(),
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
            procs.append(p)
    except BaseException:
        for p in procs:
            p.kill()
            p.wait()
        raise
    return procs


def _storm_go_barrier(procs, timeout=300):
    """Wait for every worker's ``{"ready": ...}`` line (imports done,
    client constructed), then broadcast "go" — the measurement window must
    cover concurrent *transfers*, not 32 interpreters booting on a small
    machine. -> the go wall-clock, or None if any worker died first."""
    import select

    deadline = time.monotonic() + timeout
    for p in procs:
        r, _, _ = select.select(
            [p.stdout], [], [], max(deadline - time.monotonic(), 0)
        )
        line = p.stdout.readline() if r else None
        if not line or not json.loads(line).get("ready"):
            return None
    go = time.time()
    for p in procs:
        p.stdin.write("go\n")
        p.stdin.flush()
    return go


def _collect_workers(procs, timeout_each=600):
    """-> one parsed result dict (or None) per worker."""
    import subprocess
    import sys

    out = []
    for p in procs:
        try:
            stdout, stderr = p.communicate(timeout=timeout_each)
        except subprocess.TimeoutExpired:
            p.kill()
            stdout, stderr = p.communicate()
        line = None
        for ln in reversed((stdout or "").strip().splitlines()):
            if ln.startswith("{"):
                line = ln
                break
        if line is None:
            print(
                f"storm worker died: {(stderr or '')[-500:]}", file=sys.stderr
            )
            out.append(None)
            continue
        out.append(json.loads(line))
    return out


def serve_storm_worker():
    """One storm client process. Modes: ``fetch`` = n sequential full
    fetches into fresh stores (a clone's transfer path, timed per request);
    ``resilient`` = one clone that must complete even if the server dies
    mid-transfer — retries `kart fetch` (the ROBUSTNESS.md §3 resume lanes)
    until the store is whole. Protocol: print ``{"ready": true}`` once
    imports are paid, block until the driver's "go" line, then run."""
    import sys

    i = sys.argv.index("--serve-storm-worker")
    url, base, n_requests, mode = sys.argv[i + 1 : i + 5]
    n_requests = int(n_requests)

    from kart_tpu.core.repo import KartRepo

    os.makedirs(base, exist_ok=True)
    if hasattr(os, "sched_setaffinity") and os.environ.get(
        "KART_BENCH_STORM_PIN", "1"
    ) != "0":
        # round-robin core pinning (worker index is the dir suffix): 32
        # CPU-bound drains migrating freely across a 2-core host churn
        # caches; pinning halves the migration thrash
        try:
            cpus = sorted(os.sched_getaffinity(0))
            idx = int(re.sub(r"\D", "", os.path.basename(base)) or 0)
            os.sched_setaffinity(0, {cpus[idx % len(cpus)]})
        except (OSError, ValueError) as e:
            print(f"storm worker pin failed: {e}", file=sys.stderr)
    print(json.dumps({"ready": True}), flush=True)
    sys.stdin.readline()  # the storm barrier: all clients hit at once

    if mode == "fetch":
        from kart_tpu.transport.http import HttpRemote
        from kart_tpu.transport.retry import RetryPolicy

        # patient policy: when the server sheds under the storm
        # (429 + Retry-After), a real client waits its turn — the paced
        # queue is the designed behaviour, not a failure
        policy = RetryPolicy(attempts=60, base_delay=0.05, max_delay=0.5)
        durations = []
        ok = True
        start = time.time()
        for i in range(n_requests):
            t0 = time.perf_counter()
            try:
                client = HttpRemote(url, retry=policy)
                dst = KartRepo.init_repository(os.path.join(base, f"r{i}"))
                wants = list(client.ls_refs()["heads"].values())
                client.fetch_pack(dst, wants)
            except Exception as e:
                print(f"storm request failed: {e}", file=sys.stderr)
                ok = False
                break
            durations.append(time.perf_counter() - t0)
        print(
            json.dumps(
                {
                    "ok": ok,
                    "durations": durations,
                    "start": start,
                    "end": time.time(),
                }
            ),
            flush=True,
        )
        return

    from kart_tpu import transport
    from kart_tpu.transport.remote import add_remote

    repo = KartRepo.init_repository(os.path.join(base, "clone"))
    add_remote(repo, "origin", url)
    deadline = time.time() + float(
        os.environ.get("KART_BENCH_STORM_FAULT_DEADLINE", 180)
    )
    attempts, done = 0, False
    while time.time() < deadline and not done:
        attempts += 1
        try:
            transport.fetch(repo, "origin")
            done = repo.refs.get("refs/remotes/origin/main") is not None
        except Exception as e:
            # the server being killed mid-storm IS the scenario: keep
            # resuming until it comes back (salvage + exclusion resume)
            print(f"fetch attempt {attempts}: {e}", file=sys.stderr)
            time.sleep(0.5)
    print(
        json.dumps(
            {"ok": done, "attempts": attempts, "start": 0, "end": time.time()}
        ),
        flush=True,
    )


def _prom_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _server_verb_hist(stats_doc, name, verb):
    """The labelled histogram dict (count/sum/p50/p99/buckets) from a
    ``/api/v1/stats?format=json`` document, or None."""
    for n, labels, h in stats_doc.get("snapshot", {}).get("histograms", ()):
        if n == name and labels.get("verb") == verb:
            return h
    return None


def _latency_bucket_index(value):
    """Index of ``value`` on the telemetry bucket ladder — the agreement
    check between server-estimated and client-measured percentiles is
    'same bucket ± 1' (the documented quantile error bound)."""
    from bisect import bisect_left

    from kart_tpu.telemetry.core import BUCKET_BOUNDS

    return bisect_left(BUCKET_BOUNDS, value)


def serve_storm_main():
    """The concurrent-serving bench: aggregate clone throughput of N
    simultaneous clients vs a serial cache-disabled baseline (the
    pre-ISSUE-7 behaviour: one full ObjectEnumerator walk per request),
    p99 request latency, the enum-cache hit rate, and a
    kill-the-server-mid-storm fault leg where every client must complete
    by resuming. Prints one JSON record (twice: before and after the
    fault leg, so a watchdog kill still salvages the throughput half)."""
    import math
    import sys
    import tempfile
    from urllib.request import urlopen

    rows = int(os.environ.get("KART_BENCH_STORM_ROWS", 20_000))
    clients = int(os.environ.get("KART_BENCH_STORM_CLIENTS", 32))
    per_client = int(os.environ.get("KART_BENCH_STORM_REQUESTS", 2))
    serial_reqs = int(os.environ.get("KART_BENCH_STORM_SERIAL", 4))
    fault_clients = int(os.environ.get("KART_BENCH_STORM_FAULT_CLIENTS", 8))

    from kart_tpu.synth import synth_repo

    # a RAM-backed working set when available: the bench measures the
    # server's concurrency, and 32 colocated client drains fsync'ing packs
    # through a slow container filesystem (9p on the dev boxes) would
    # serialise on the mount instead of exercising the server
    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=shm) as td:
        src, _ = synth_repo(
            os.path.join(td, "src"), rows, blobs="real", edit_frac=0.0
        )
        workdir = src.workdir or src.gitdir

        record = {
            "metric": "serve_storm",
            "serve_storm_rows": rows,
            "serve_storm_clients": clients,
            "serve_storm_requests_total": clients * per_client,
            "ok": True,
        }

        # -- serial baseline: 1 client x sequential requests, cache OFF
        port = _free_port()
        server = _spawn_serve(workdir, port, {"KART_SERVE_ENUM_CACHE": "0"})
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = _spawn_storm_workers(
                url, os.path.join(td, "serial"), 1, serial_reqs, "fetch"
            )
            _storm_go_barrier(procs)
            serial_results = _collect_workers(procs)
            with urlopen(url + "api/v1/stats?format=json", timeout=10) as resp:
                serial_stats_doc = json.loads(resp.read().decode())
        finally:
            server.kill()
            server.wait()
        r0 = serial_results[0]
        if not r0 or not r0["ok"] or not r0["durations"]:
            record["ok"] = False
            print(json.dumps(record), flush=True)
            return
        serial_req_s = sum(r0["durations"]) / len(r0["durations"])
        serial_rate = rows / serial_req_s
        record["serve_storm_serial_features_per_sec"] = round(serial_rate)
        # the coupled-regime agreement check: one uncached client, so each
        # request is dominated by the server's own walk+spool+stream — the
        # server-estimated p99 must land within one log bucket of the
        # client-measured one (the documented quantile error bound)
        serial_hist = _server_verb_hist(
            serial_stats_doc, "server.request_seconds", "fetch-pack"
        )
        if serial_hist is not None:
            client_p99 = sorted(r0["durations"])[
                min(
                    len(r0["durations"]) - 1,
                    math.ceil(0.99 * len(r0["durations"])) - 1,
                )
            ]
            record["serve_serial_server_p99_seconds"] = round(
                serial_hist["p99"], 3
            )
            serial_distance = abs(
                _latency_bucket_index(serial_hist["p99"])
                - _latency_bucket_index(client_p99)
            )
            record["serve_serial_p99_bucket_distance"] = serial_distance
            record["serve_serial_server_p99_agrees"] = serial_distance <= 1

        # -- the storm: N concurrent clients, cache ON. An inflight cap is
        # available (KART_BENCH_STORM_INFLIGHT > 0 arms the shedder on the
        # storm server; the patient worker policy rides the 429s) but is
        # off by default: on a small colocated host the shed/retry round
        # trips cost more than the scheduler thrash they avoid — the cap
        # exists for measuring the shed path itself, not for throughput
        inflight_cap = os.environ.get("KART_BENCH_STORM_INFLIGHT", "0")
        port = _free_port()
        server = _spawn_serve(
            workdir,
            port,
            {
                "KART_SERVE_MAX_INFLIGHT": inflight_cap,
                "KART_SERVE_RETRY_AFTER": "0",
            },
        )
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = _spawn_storm_workers(
                url, os.path.join(td, "storm"), clients, per_client, "fetch"
            )
            go = _storm_go_barrier(procs)
            storm_results = _collect_workers(procs)
            with urlopen(url + "api/v1/stats", timeout=10) as resp:
                stats_text = resp.read().decode()
            # the server's own view: per-verb bucketed latency histograms
            # with quantile estimates (docs/OBSERVABILITY.md §9)
            with urlopen(url + "api/v1/stats?format=json", timeout=10) as resp:
                stats_doc = json.loads(resp.read().decode())
        finally:
            server.kill()
            server.wait()
        good = [r for r in storm_results if r and r["ok"]]
        record["ok"] = record["ok"] and go is not None and len(good) == clients
        durations = sorted(d for r in good for d in r["durations"])
        if not durations or go is None:
            record["ok"] = False
            print(json.dumps(record), flush=True)
            return
        window = max(r["end"] for r in good) - go
        agg_rate = rows * len(durations) / max(window, 1e-9)
        record["serve_storm_agg_features_per_sec"] = round(agg_rate)
        record["serve_storm_speedup_vs_serial"] = round(
            agg_rate / serial_rate, 2
        )
        p99_idx = min(
            len(durations) - 1, math.ceil(0.99 * len(durations)) - 1
        )
        record["serve_storm_p99_request_seconds"] = round(
            durations[p99_idx], 3
        )
        # server-reported percentiles from the bucketed fetch-pack request
        # histogram — the server's tail is no longer a number only bench.py
        # can compute. The storm-leg distance is informational on a small
        # colocated host: with the enum cache on, a hit is a memcpy into
        # kernel socket buffers and the client's wall-clock adds N-process
        # scheduler queueing the server never sees (both numbers are true;
        # the coupled-regime agreement bound is asserted on the serial leg
        # above, and in tier-1 with the cache off)
        server_hist = _server_verb_hist(
            stats_doc, "server.request_seconds", "fetch-pack"
        )
        if server_hist is not None:
            record["serve_storm_server_p50_seconds"] = round(
                server_hist["p50"], 3
            )
            record["serve_storm_server_p99_seconds"] = round(
                server_hist["p99"], 3
            )
            distance = abs(
                _latency_bucket_index(server_hist["p99"])
                - _latency_bucket_index(durations[p99_idx])
            )
            record["serve_storm_server_p99_bucket_distance"] = distance
            record["serve_storm_server_p99_agrees"] = distance <= 1
        hits = _prom_value(stats_text, "kart_server_enum_cache_hits_total")
        misses = _prom_value(stats_text, "kart_server_enum_cache_misses_total")
        record["serve_enum_cache_hit_rate"] = round(
            hits / (hits + misses) if hits + misses else 0.0, 4
        )
        print(json.dumps(record), flush=True)

        # -- ceiling-context leg: the same 64 requests from as many
        # colocated clients as the host can actually run (the bench puts
        # every client on the server's own cores; on a 2-core container 32
        # CPU-bound drains measure scheduler thrash, not the server —
        # MULTICHIP r06's env-ceiling precedent). Same server config.
        ceil_clients = int(
            os.environ.get("KART_BENCH_STORM_CEILING_CLIENTS", 8)
        )
        ceil_reqs = max(1, (clients * per_client) // max(1, ceil_clients))
        port = _free_port()
        server = _spawn_serve(
            workdir, port, {"KART_SERVE_MAX_INFLIGHT": inflight_cap}
        )
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = _spawn_storm_workers(
                url, os.path.join(td, "ceil"), ceil_clients, ceil_reqs,
                "fetch",
            )
            go = _storm_go_barrier(procs)
            ceil_results = _collect_workers(procs)
        finally:
            server.kill()
            server.wait()
        cgood = [r for r in ceil_results if r and r["ok"]]
        cdur = [d for r in cgood for d in r["durations"]]
        record["serve_storm_ceiling_clients"] = ceil_clients
        if cdur and go is not None and len(cgood) == ceil_clients:
            cagg = rows * len(cdur) / max(
                max(r["end"] for r in cgood) - go, 1e-9
            )
            record["serve_storm_ceiling_agg_features_per_sec"] = round(cagg)
            record["serve_storm_ceiling_speedup_vs_serial"] = round(
                cagg / serial_rate, 2
            )
        print(json.dumps(record), flush=True)

        # -- fault leg: SIGKILL the server mid-storm, restart it; every
        # client must complete via the resume lanes (zero failed clients)
        port = _free_port()
        server = _spawn_serve(workdir, port)
        ok_clients = 0
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = _spawn_storm_workers(
                url, os.path.join(td, "fault"), fault_clients, 1, "resilient"
            )
            go = _storm_go_barrier(procs)
            if go is None:
                raise RuntimeError("fault-leg workers failed to start")
            pause = max(0.3, serial_req_s * 0.5)  # mid-transfer
            time.sleep(pause)
            server.kill()
            server.wait()
            time.sleep(1.0)
            server = _spawn_serve(workdir, port)
            fault_results = _collect_workers(procs)
            ok_clients = sum(1 for r in fault_results if r and r["ok"])
        finally:
            server.kill()
            server.wait()
        record["serve_storm_fault_clients"] = fault_clients
        record["serve_storm_fault_clients_ok"] = ok_clients
        record["ok"] = record["ok"] and ok_clients == fault_clients
        print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# --merge-storm: K writers hammering one branch (ISSUE 9, docs/SERVING.md §6)
# ---------------------------------------------------------------------------


def _storm_edit_commit(repo, ds_path, *, deletes=(), updates=(), message="edit"):
    """Build + commit a tiny feature diff (the shared helper in
    kart_tpu.synth; tests/helpers.edit_commit rides the same one)."""
    from kart_tpu.synth import commit_feature_edits

    return commit_feature_edits(
        repo, ds_path, deletes=deletes, updates=updates, message=message
    )


def merge_storm_worker():
    """One storm writer process. argv after the flag:
    ``url base n_commits mode fid_base``. Modes:

    * ``disjoint`` — each commit deletes its own feature; every push must
      land (the server rebases CAS losers), counting wire attempts so the
      driver can compute retry amplification, and collecting each push's
      server-reported merge-queue wait.
    * ``overlap`` — one commit updating feature 1 (every writer collides):
      exactly one writer lands, the rest must be rejected terminally after
      exactly one attempt.
    * ``resilient`` — disjoint edits pushed through transport.push with
      patient outer retries: the server being SIGKILLed mid-storm is the
      scenario; the writer must land once it returns.
    """
    import sys

    i = sys.argv.index("--merge-storm-worker")
    url, base, n_commits, mode, fid_base = sys.argv[i + 1 : i + 6]
    n_commits, fid_base = int(n_commits), int(fid_base)

    from kart_tpu import transport
    from kart_tpu.transport.http import (
        HttpRemote,
        HttpTransportError,
        have_closure,
    )
    from kart_tpu.transport.protocol import ObjectEnumerator
    from kart_tpu.transport.retry import RetryPolicy

    os.makedirs(base, exist_ok=True)
    if hasattr(os, "sched_setaffinity") and os.environ.get(
        "KART_BENCH_STORM_PIN", "1"
    ) != "0":
        try:
            cpus = sorted(os.sched_getaffinity(0))
            idx = int(re.sub(r"\D", "", os.path.basename(base)) or 0)
            os.sched_setaffinity(0, {cpus[idx % len(cpus)]})
        except (OSError, ValueError) as e:
            print(f"storm worker pin failed: {e}", file=sys.stderr)

    repo = transport.clone(url, os.path.join(base, "clone"), do_checkout=False)
    repo.config.set_many(
        {"user.name": os.path.basename(base), "user.email": "w@storm"}
    )
    ds_path = "synth"
    # synth pks are hashed ints, not 1..n: ``fid_base`` indexes the sorted
    # pk list (identical in every clone of one leg, so index ranges stay
    # disjoint across writers)
    pks = sorted(
        f["fid"] for f in repo.datasets("HEAD")[ds_path].features()
    )
    print(json.dumps({"ready": True}), flush=True)
    sys.stdin.readline()  # the storm barrier

    out = {
        "ok": True, "landed": 0, "attempts": 0, "conflicts": 0,
        "cas_failures": 0, "queue_waits": [], "push_seconds": [],
        "start": time.time(),
    }

    def push_once(client, new_oid, prev_oid):
        """One wire push attempt with the freshly observed tip as CAS base;
        -> the server's full receive payload."""
        info = client.ls_refs()
        old = info["heads"].get("main")
        # the server provably holds our previously-landed commit: its
        # closure (not the unknown server merge commits) prunes the pack
        has = have_closure(repo.odb, [prev_oid] if prev_oid else [], ())
        enum = ObjectEnumerator(repo.odb, [new_oid], has=has.__contains__)
        return client.receive_pack(
            enum,
            [{"ref": "refs/heads/main", "old": old, "new": new_oid,
              "force": False}],
        )

    if mode == "resilient":
        deadline = time.time() + float(
            os.environ.get("KART_BENCH_STORM_FAULT_DEADLINE", 180)
        )
        oid = _storm_edit_commit(
            repo, ds_path, deletes=[pks[fid_base]],
            message=f"resilient {fid_base}",
        )
        done = False
        while time.time() < deadline and not done:
            out["attempts"] += 1
            try:
                transport.push(repo, "origin")
                done = True
            except Exception as e:
                # the killed/restarting server IS the scenario: keep trying
                print(f"push attempt failed: {e}", file=sys.stderr)
                time.sleep(0.5)
        out["ok"] = done
        out["landed"] = int(done)
        out["end"] = time.time()
        print(json.dumps(out), flush=True)
        return

    client = HttpRemote(url, retry=RetryPolicy(attempts=1))
    prev = None
    for j in range(n_commits):
        if mode == "overlap":
            # every writer rewrites the SAME feature with its own value
            new_oid = _storm_edit_commit(
                repo, ds_path,
                updates=[{"fid": pks[0], "rating": 1000.0 + fid_base}],
                message=f"overlap {fid_base}",
            )
        else:
            new_oid = _storm_edit_commit(
                repo, ds_path, deletes=[pks[fid_base + j]],
                message=f"disjoint {fid_base + j}",
            )
        landed = False
        t0 = time.perf_counter()
        for _ in range(60):
            out["attempts"] += 1
            try:
                result = push_once(client, new_oid, prev)
                landed = True
                rebase = result.get("rebase") or {}
                out["queue_waits"].append(
                    float(rebase.get("queue_wait_seconds") or 0.0)
                )
                break
            except HttpTransportError as e:
                if getattr(e, "terminal", False) and getattr(
                    e, "conflict_report", None
                ):
                    out["conflicts"] += 1
                    break  # terminal: exactly this one attempt, no re-push
                if getattr(e, "shed", False):
                    time.sleep(min(float(e.retry_after or 0.1), 2.0))
                    continue
                if "moved" in str(e) or "fast-forward" in str(e):
                    # the failure the merge service exists to remove
                    out["cas_failures"] += 1
                    continue
                print(f"push failed: {e}", file=sys.stderr)
                out["ok"] = False
                break
        out["push_seconds"].append(time.perf_counter() - t0)
        if landed:
            out["landed"] += 1
            prev = new_oid
        elif mode != "overlap":
            out["ok"] = False
            break
    out["end"] = time.time()
    print(json.dumps(out), flush=True)


def merge_storm_main():
    """The contended-writer bench (docs/SERVING.md §6): K writer processes
    hammering one branch through `kart serve`. Legs: disjoint-feature
    commits (all must land, zero client-visible CAS failures, retry
    amplification ~1), an overlapping-feature leg (conflicts rejected
    terminally after exactly one attempt), and a SIGKILL-the-server
    mid-storm leg (every writer lands once it returns). Prints the record
    after each leg so a watchdog kill salvages the finished legs."""
    import math
    import subprocess
    import sys
    import tempfile
    from urllib.request import urlopen

    writers = int(os.environ.get("KART_BENCH_MERGE_WRITERS", 8))
    per_writer = int(os.environ.get("KART_BENCH_MERGE_COMMITS", 3))
    rows = int(os.environ.get("KART_BENCH_MERGE_ROWS", 3000))
    fault_writers = int(os.environ.get("KART_BENCH_MERGE_FAULT_WRITERS", 6))

    from kart_tpu.synth import synth_repo

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=shm) as td:
        src, _ = synth_repo(
            os.path.join(td, "src"), rows, blobs="real", edit_frac=0.0
        )
        src.config["receive.denyCurrentBranch"] = "ignore"
        workdir = src.workdir or src.gitdir

        record = {
            "metric": "merge_storm",
            "merge_storm_writers": writers,
            "merge_storm_commits_total": writers * per_writer,
            "ok": True,
        }

        def spawn_writers(url, leg, n, n_commits, mode, fid0, fid_stride):
            # each leg owns a disjoint fid range of the shared source repo:
            # a writer deleting a feature another leg already removed would
            # fail locally, not exercise the server
            procs = []
            try:
                for i in range(n):
                    p = subprocess.Popen(
                        [
                            sys.executable, os.path.abspath(__file__),
                            "--merge-storm-worker", url,
                            os.path.join(td, leg, f"w{i}"),
                            str(n_commits), mode, str(fid0 + i * fid_stride),
                        ],
                        env=_storm_env(),
                        stdin=subprocess.PIPE,
                        stdout=subprocess.PIPE,
                        stderr=subprocess.PIPE,
                        text=True,
                    )
                    procs.append(p)
            except BaseException:
                for p in procs:
                    p.kill()
                    p.wait()
                raise
            return procs

        # -- disjoint leg: all land, zero client-visible CAS failures
        port = _free_port()
        server = _spawn_serve(workdir, port)
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = spawn_writers(
                url, "disjoint", writers, per_writer, "disjoint", 2, per_writer
            )
            go = _storm_go_barrier(procs)
            results = _collect_workers(procs)
            with urlopen(url + "api/v1/stats", timeout=10) as resp:
                stats_text = resp.read().decode()
        finally:
            server.kill()
            server.wait()
        good = [r for r in results if r and r["ok"]]
        landed = sum(r["landed"] for r in good)
        attempts = sum(r["attempts"] for r in good)
        cas = sum(r["cas_failures"] for r in good)
        window = (
            max((r["end"] for r in good), default=0) - go if go else 0.0
        )
        record["merge_storm_commits_landed"] = landed
        record["merge_storm_client_attempts"] = attempts
        record["merge_storm_cas_failures_client_visible"] = cas
        record["merge_storm_commits_per_sec"] = round(
            landed / max(window, 1e-9), 2
        )
        record["merge_storm_retry_amplification"] = round(
            attempts / max(landed, 1), 3
        )
        waits = sorted(w for r in good for w in r["queue_waits"])
        p99 = waits[min(len(waits) - 1, math.ceil(0.99 * len(waits)) - 1)] if waits else 0.0
        record["merge_storm_queue_p99_wait_seconds"] = round(p99, 4)
        qsum = _prom_value(stats_text, "kart_server_merge_queue_wait_seconds_sum")
        qcount = _prom_value(
            stats_text, "kart_server_merge_queue_wait_seconds_count"
        )
        record["merge_storm_queue_mean_wait_seconds"] = round(
            qsum / qcount if qcount else 0.0, 4
        )
        record["merge_storm_rebases_landed"] = int(
            _prom_value(stats_text, "kart_server_rebase_landed_total")
        )
        record["ok"] = (
            record["ok"]
            and go is not None
            and len(good) == writers
            and landed == writers * per_writer
            and cas == 0
            and record["merge_storm_retry_amplification"] < 1.5
        )
        print(json.dumps(record), flush=True)

        # -- overlap leg: everyone edits feature 1; exactly one lands, the
        # rest are rejected terminally after exactly one attempt each
        port = _free_port()
        server = _spawn_serve(workdir, port)
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = spawn_writers(url, "overlap", writers, 1, "overlap", 200, 1)
            go = _storm_go_barrier(procs)
            results = _collect_workers(procs)
        finally:
            server.kill()
            server.wait()
        good = [r for r in results if r]
        landed = sum(r["landed"] for r in good)
        rejections = sum(r["conflicts"] for r in good)
        # a conflicted writer's whole budget must be one wire attempt
        reject_attempts = sum(
            r["attempts"] for r in good if r["conflicts"]
        )
        record["rebase_conflict_writers"] = writers
        record["rebase_conflict_rejections"] = rejections
        record["rebase_conflict_attempts_per_reject"] = round(
            reject_attempts / max(rejections, 1), 3
        )
        record["ok"] = (
            record["ok"]
            and landed == 1
            and rejections == writers - 1
            and record["rebase_conflict_attempts_per_reject"] == 1.0
        )
        print(json.dumps(record), flush=True)

        # -- fault leg: SIGKILL the server while contended rebases are in
        # flight, restart it; every writer must land via retries, and the
        # abandoned quarantine debris stays sweepable (never served)
        port = _free_port()
        server = _spawn_serve(workdir, port)
        ok_writers = 0
        try:
            url = f"http://127.0.0.1:{port}/"
            procs = spawn_writers(
                url, "fault", fault_writers, 1, "resilient", 400, 1
            )
            go = _storm_go_barrier(procs)
            if go is None:
                raise RuntimeError("fault-leg writers failed to start")
            time.sleep(float(os.environ.get("KART_BENCH_MERGE_KILL_AFTER", 0.8)))
            server.kill()
            server.wait()
            time.sleep(1.0)
            server = _spawn_serve(workdir, port)
            results = _collect_workers(procs)
            ok_writers = sum(1 for r in results if r and r["ok"])
        finally:
            server.kill()
            server.wait()
        record["merge_storm_fault_writers"] = fault_writers
        record["merge_storm_fault_writers_ok"] = ok_writers
        record["ok"] = record["ok"] and ok_writers == fault_writers
        print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# bench.py --tiles: tile read-serving off the columnar store (ISSUE 10)
# ---------------------------------------------------------------------------


def _tile_sample(zoom, count, seed):
    """A deterministic pseudo-random set of distinct z/x/y addresses at one
    zoom (full x range, extreme y rows excluded — the synth layout's bands
    stop at ±85°)."""
    import random

    rng = random.Random(seed)
    n = 1 << zoom
    count = min(count, n * max(1, n - 2))
    seen = set()
    out = []
    while len(out) < count:
        x = rng.randrange(n)
        y = rng.randrange(n) if n <= 2 else rng.randrange(1, n - 1)
        if (x, y) in seen:
            continue
        seen.add((x, y))
        out.append((zoom, x, y))
    return out


def tiles_storm_worker():
    """One tile-storm client: GET n random tiles (drawn from the shared
    sample, so the mix exercises hits, misses and single-flight) over
    plain HTTP, riding 429 + Retry-After like a patient map client.
    Protocol as the other storm workers: print ready, block for go."""
    import sys
    import urllib.error
    import urllib.request

    i = sys.argv.index("--tiles-storm-worker")
    url, oid, ds_path, n_requests, zoom, seed = sys.argv[i + 1 : i + 7]
    n_requests, zoom, seed = int(n_requests), int(zoom), int(seed)
    import random

    sample = _tile_sample(
        zoom, int(os.environ.get("KART_BENCH_TILES_COUNT", 64)), 7
    )
    rng = random.Random(seed)
    picks = [sample[rng.randrange(len(sample))] for _ in range(n_requests)]

    print(json.dumps({"ready": True}), flush=True)
    sys.stdin.readline()

    durations = []
    ok_requests = 0
    errors = []
    start = time.time()
    for z, x, y in picks:
        t0 = time.perf_counter()
        tile_url = f"{url}api/v1/tiles/{oid}/{ds_path}/{z}/{x}/{y}?layers=bin"
        for _attempt in range(60):
            try:
                with urllib.request.urlopen(tile_url, timeout=60) as r:
                    r.read()
                ok_requests += 1
                break
            except urllib.error.HTTPError as e:
                if e.code != 429:
                    errors.append(f"{z}/{x}/{y}: HTTP {e.code} {e.read()[:200]!r}")
                    break
                try:
                    pause = float(e.headers.get("Retry-After", "1"))
                except (TypeError, ValueError):
                    pause = 1.0
                time.sleep(min(pause, 2.0))
            except OSError as e:
                # connection-level churn (reset/refused under the accept
                # storm) is transient by nature — a real map client
                # retries it exactly like a 429
                time.sleep(0.2)
        else:
            errors.append(f"{z}/{x}/{y}: retries exhausted")
        durations.append(time.perf_counter() - t0)
    print(
        json.dumps(
            {
                "ok": ok_requests == len(picks),
                "ok_requests": ok_requests,
                "errors": errors[:5],
                "durations": durations,
                "start": start,
                "end": time.time(),
            }
        ),
        flush=True,
    )


def tiles_main():
    """`bench.py --tiles`: tiles/s cold and cached at the 100M-feature
    spatial synth repo (promised blobs ⇒ the columnar `bin` layer, the
    hot path), the block-pruning evidence (a cold tile must fault only
    boundary/in blocks), byte-identity cold vs cached, and a
    concurrent-client tile storm against a real `kart serve` process.
    Recorded in BENCH_r10.json (docs/TILES.md §7). Prints the in-process
    record before the storm so a watchdog kill still salvages the
    throughput half."""
    import sys
    import tempfile

    rows = int(os.environ.get("KART_BENCH_TILES_ROWS", 100_000_000))
    n_tiles = int(os.environ.get("KART_BENCH_TILES_COUNT", 64))
    zoom = int(os.environ.get("KART_BENCH_TILES_ZOOM", 7))
    clients = int(os.environ.get("KART_BENCH_TILES_CLIENTS", 16))
    per_client = int(os.environ.get("KART_BENCH_TILES_REQUESTS", 50))

    from kart_tpu import telemetry, tiles
    from kart_tpu.synth import synth_repo

    # bench tiles at shallow zooms can exceed the serving default ceiling;
    # the ceiling is a client-protocol concern, not what's being measured
    os.environ["KART_TILE_MAX_FEATURES"] = "0"

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=shm) as td:
        t0 = time.perf_counter()
        repo, info = synth_repo(
            os.path.join(td, "repo"), rows, spatial=True, blobs="promised"
        )
        synth_s = time.perf_counter() - t0
        oid = info["edit_commit"]
        record = {
            "metric": "tiles",
            "tile_rows": rows,
            "tile_zoom": zoom,
            "tile_count": n_tiles,
            "tile_synth_seconds": round(synth_s, 2),
            "ok": True,
        }

        def counters():
            out = {}
            for name, labels, value in telemetry.snapshot()["counters"]:
                if not labels:
                    out[name] = value
            return out

        telemetry.reset(disable=False)
        telemetry.enable(metrics=True)
        sample = _tile_sample(zoom, n_tiles, 7)

        # -- cold: every tile is a miss (fresh cache, fresh sources)
        payloads = {}
        t0 = time.perf_counter()
        for z, x, y in sample:
            payloads[(z, x, y)], _, cached = tiles.serve_tile(
                repo, oid, "synth", z, x, y, layers="bin"
            )
            assert not cached
        cold_s = time.perf_counter() - t0
        c = counters()
        from kart_tpu.diff.sidecar import AGG_BLOCK_ROWS

        # the dataset's sidecar block count — the denominator every tile's
        # pruning classifies against
        dataset_blocks_total = -(-rows // AGG_BLOCK_ROWS)
        record["tiles_per_sec_cold"] = round(n_tiles / cold_s, 2)
        record["tile_blocks_total"] = dataset_blocks_total
        record["tile_blocks_read_mean"] = round(
            c.get("tiles.blocks_read", 0) / n_tiles, 1
        )
        denom = c.get("tiles.blocks_read", 0) + c.get("tiles.blocks_pruned", 0)
        record["tile_blocks_pruned_pct"] = round(
            100.0 * c.get("tiles.blocks_pruned", 0) / max(1, denom), 2
        )
        record["tile_features_mean"] = round(
            c.get("tiles.features_out", 0) / n_tiles, 1
        )

        # -- cached: the same tiles again, byte-identical by contract
        before = counters()
        identical = True
        t0 = time.perf_counter()
        for z, x, y in sample:
            payload, _, cached = tiles.serve_tile(
                repo, oid, "synth", z, x, y, layers="bin"
            )
            identical = identical and cached and payload == payloads[(z, x, y)]
        cached_s = time.perf_counter() - t0
        c = counters()
        record["tiles_per_sec_cached"] = round(n_tiles / cached_s, 2)
        record["tile_payload_identical"] = bool(identical)
        # hit rate of the CACHED pass alone (counter delta): the cold pass
        # is all misses by construction and would halve the reported rate
        d_hits = c.get("tiles.cache.hits", 0) - before.get("tiles.cache.hits", 0)
        d_miss = c.get("tiles.cache.misses", 0) - before.get(
            "tiles.cache.misses", 0
        )
        record["tile_cache_hit_rate"] = round(d_hits / max(1, d_hits + d_miss), 4)
        record["ok"] = record["ok"] and identical
        print(json.dumps(record), flush=True)

        # -- encoding ladder (ISSUE 15): bytes/feature per layer at the
        # same sample. KTB1 bytes come from the cold-leg payloads; ktb2 and
        # mvt are fresh keys (cold encodes through the stream codecs). The
        # acceptance ratio is ktb2 vs KTB1 *alone* — stricter than the
        # issue's "KTB1+geojson" bound (geojson only adds bytes, and the
        # 100M synth's blobs are promised).
        from kart_tpu.tiles.encode import parse_payload as _parse_payload

        layer_bytes = {"bin": 0, "ktb2": 0, "mvt": 0}
        features_total = 0
        for (z, x, y), payload in payloads.items():
            header, lb = _parse_payload(payload)
            layer_bytes["bin"] += len(lb["bin"])
            features_total += header["count"]
        t0 = time.perf_counter()
        for z, x, y in sample:
            payload, _, _ = tiles.serve_tile(
                repo, oid, "synth", z, x, y, layers="ktb2"
            )
            layer_bytes["ktb2"] += len(_parse_payload(payload)[1]["ktb2"])
        ktb2_s = time.perf_counter() - t0
        for z, x, y in sample:
            payload, _, _ = tiles.serve_tile(
                repo, oid, "synth", z, x, y, layers="mvt"
            )
            layer_bytes["mvt"] += len(_parse_payload(payload)[1]["mvt"])
        # geom: real ring geometry off the sidecar vertex column, per-zoom
        # simplified (docs/TILES.md §6) — box features, so bytes/feature
        # should land near mvt's (same shapes, real command encoding)
        layer_bytes["geom"] = 0
        t0 = time.perf_counter()
        for z, x, y in sample:
            payload, _, _ = tiles.serve_tile(
                repo, oid, "synth", z, x, y, layers="geom"
            )
            layer_bytes["geom"] += len(_parse_payload(payload)[1]["geom"])
        geom_s = time.perf_counter() - t0
        ft = max(1, features_total)
        record["tile_bytes_per_feature_ktb1"] = round(layer_bytes["bin"] / ft, 2)
        record["tile_bytes_per_feature_ktb2"] = round(layer_bytes["ktb2"] / ft, 2)
        record["tile_bytes_per_feature_mvt"] = round(layer_bytes["mvt"] / ft, 2)
        record["tile_bytes_per_feature_geom"] = round(
            layer_bytes["geom"] / ft, 2
        )
        record["tiles_per_sec_ktb2_cold"] = round(n_tiles / ktb2_s, 2)
        record["tiles_per_sec_geom_cold"] = round(n_tiles / geom_s, 2)
        record["tile_ktb2_vs_ktb1"] = round(
            layer_bytes["bin"] / max(1, layer_bytes["ktb2"]), 2
        )
        record["tile_ktb2_meets_2x"] = (
            layer_bytes["bin"] >= 2 * layer_bytes["ktb2"]
        )
        record["ok"] = record["ok"] and record["tile_ktb2_meets_2x"]
        print(json.dumps(record), flush=True)

        # -- pyramid export, 1 worker vs N (ISSUE 15): the parallel
        # encoder over one whole zoom level, byte-identity asserted across
        # worker counts, speedup reported next to the measured 2-process
        # env ceiling (a ~1.5x-ceiling container can't show 2x — cf.
        # MULTICHIP_r06 / BENCH_r07 precedent)
        export_zooms = [
            int(v)
            for v in os.environ.get("KART_BENCH_EXPORT_ZOOMS", "7").split("-")
        ]
        export_zooms = list(range(export_zooms[0], export_zooms[-1] + 1))
        n_workers = max(2, os.cpu_count() or 2)
        src = tiles.source_for(repo, oid, "synth")
        from kart_tpu.tiles.pyramid import export_pyramid

        def _export(workers, out):
            t0 = time.perf_counter()
            stats = export_pyramid(
                src, export_zooms, out, layers=("ktb2",), workers=workers,
                max_features=0,
            )
            return time.perf_counter() - t0, stats

        from kart_tpu.tiles.pyramid import tree_digest as _tree_digest

        s1, stats1 = _export(1, os.path.join(td, "pyr1"))
        sn, statsn = _export(n_workers, os.path.join(td, "pyrN"))
        record["pyramid_export_zoom"] = export_zooms[-1]
        record["pyramid_export_tiles"] = stats1["tiles_written"]
        record["pyramid_export_seconds_1w"] = round(s1, 2)
        record["pyramid_export_seconds_nw"] = round(sn, 2)
        record["pyramid_export_workers"] = statsn["export_workers"]
        record["pyramid_export_speedup"] = round(s1 / max(sn, 1e-9), 2)
        record["pyramid_export_identical"] = _tree_digest(
            os.path.join(td, "pyr1")
        ) == _tree_digest(os.path.join(td, "pyrN"))
        cpus = (
            sorted(os.sched_getaffinity(0))
            if hasattr(os, "sched_getaffinity")
            else [0]
        )
        record["pyramid_export_env_ceiling"] = _env_2proc_scaling(
            _ALU_TASK, cpus
        )
        record["ok"] = record["ok"] and record["pyramid_export_identical"]
        print(json.dumps(record), flush=True)

        # -- the storm: N clients hammering a real `kart serve` process
        workdir = repo.workdir or repo.gitdir
        port = _free_port()
        server = _spawn_serve(
            workdir, port, {"KART_TILE_MAX_FEATURES": "0"}
        )
        procs = []
        try:
            url = f"http://127.0.0.1:{port}/"
            for i in range(clients):
                procs.append(
                    subprocess_popen_tile_worker(
                        url, oid, per_client, zoom, 100 + i
                    )
                )
            go = _storm_go_barrier(procs)
            results = _collect_workers(procs)
        finally:
            server.kill()
            server.wait()
        good = [r for r in results if r]
        durations = sorted(d for r in good for d in r["durations"])
        ok_requests = sum(r.get("ok_requests", 0) for r in good)
        errs = [e for r in good for e in r.get("errors", [])]
        if errs:
            print("tile storm errors: " + " | ".join(errs[:8]), file=sys.stderr)
        record["tile_storm_clients"] = clients
        record["tile_storm_requests_total"] = clients * per_client
        record["tile_storm_ok_requests"] = ok_requests
        if durations and go is not None:
            wall = max(r["end"] for r in good) - go
            record["tile_storm_agg_tiles_per_sec"] = round(
                ok_requests / max(wall, 1e-9), 2
            )
            record["tile_storm_p99_request_seconds"] = round(
                durations[min(len(durations) - 1, int(0.99 * len(durations)))], 4
            )
        else:
            record["ok"] = False
            record["tile_storm_agg_tiles_per_sec"] = 0
            record["tile_storm_p99_request_seconds"] = 0
        record["ok"] = record["ok"] and ok_requests == clients * per_client
        print(json.dumps(record), flush=True)


def subprocess_popen_tile_worker(url, oid, n_requests, zoom, seed):
    import subprocess
    import sys

    return subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--tiles-storm-worker", url, oid, "synth",
            str(n_requests), str(zoom), str(seed),
        ],
        env=_storm_env(),
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


# ---------------------------------------------------------------------------
# bench.py --fleet: M replicas × N clients (ISSUE 13, docs/FLEET.md §6)
# ---------------------------------------------------------------------------


def fleet_tile_worker():
    """One fleet tile client: GET n tiles from ONE replica over a
    keep-alive HTTP/1.1 connection (a map client holds its connection; a
    fresh TCP handshake per cached-tile memcpy would measure the kernel,
    not the fleet). argv after the flag: ``url oid ds n_requests zoom
    seed``. Protocol as the other storm workers: ready / go / one JSON
    result line."""
    import http.client
    import sys
    from urllib.parse import urlsplit

    i = sys.argv.index("--fleet-tile-worker")
    url, oid, ds_path, n_requests, zoom, seed = sys.argv[i + 1 : i + 7]
    n_requests, zoom, seed = int(n_requests), int(zoom), int(seed)
    import random

    sample = _tile_sample(
        zoom, int(os.environ.get("KART_BENCH_FLEET_TILE_COUNT", 48)), 7
    )
    rng = random.Random(seed)
    picks = [sample[rng.randrange(len(sample))] for _ in range(n_requests)]
    netloc = urlsplit(url).netloc

    print(json.dumps({"ready": True}), flush=True)
    sys.stdin.readline()

    conn = http.client.HTTPConnection(netloc, timeout=60)
    durations = []
    ok_requests = 0
    errors = []
    start = time.time()
    for z, x, y in picks:
        path = f"/api/v1/tiles/{oid}/{ds_path}/{z}/{x}/{y}?layers=bin"
        t0 = time.perf_counter()
        for _attempt in range(60):
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                body = resp.read()
                if resp.status == 200:
                    ok_requests += 1
                    break
                if resp.status == 429:
                    try:
                        pause = float(resp.headers.get("Retry-After", "1"))
                    except (TypeError, ValueError):
                        pause = 1.0
                    time.sleep(min(pause, 2.0))
                    continue
                errors.append(f"{z}/{x}/{y}: HTTP {resp.status} {body[:120]!r}")
                break
            except OSError:
                # connection churn: reconnect and retry, like a map client
                conn.close()
                conn = http.client.HTTPConnection(netloc, timeout=60)
                time.sleep(0.1)
        else:
            errors.append(f"{z}/{x}/{y}: retries exhausted")
        durations.append(time.perf_counter() - t0)
    conn.close()
    print(
        json.dumps(
            {
                "ok": ok_requests == len(picks),
                "ok_requests": ok_requests,
                "errors": errors[:5],
                "durations": durations,
                "start": start,
                "end": time.time(),
            }
        ),
        flush=True,
    )


def _fleet_refs(url, timeout=10):
    from urllib.request import urlopen

    with urlopen(f"{url}api/v1/refs", timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _fleet_stats_json(url, timeout=10):
    from urllib.request import urlopen

    with urlopen(f"{url}api/v1/stats?format=json", timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _fleet_counter(stats_doc, name):
    return sum(
        v
        for n, _labels, v in stats_doc.get("snapshot", {}).get("counters", ())
        if n == name
    )


def _fleet_store_digest(path):
    """refs + object-store content digest of the repo at ``path`` —
    byte-identical convergence means equal tuples (oid = content address,
    so the sorted oid set pins every object byte)."""
    import hashlib

    from kart_tpu.core.repo import KartRepo

    repo = KartRepo(path)
    refs = dict(repo.refs.iter_refs("refs/"))
    h = hashlib.sha256()
    for oid in sorted(repo.odb.iter_oids()):
        h.update(oid.encode())
    return refs, h.hexdigest()


def fleet_main():
    """`bench.py --fleet` (docs/FLEET.md §6): a primary + M pull-replicas
    serving N clients. Legs: (1) aggregate cached tiles/s across the
    replica fleet (vs the single-node BENCH_r10 cached number) with the
    peer-cache hit rate; (2) aggregate clone throughput fanned across
    replicas; (3) replication lag — push-ack to replica-visible — p99;
    (4) the failover drill: SIGKILL the primary mid-write-storm, restart
    it, and prove zero acked commits were lost and both replicas converge
    byte-identical (refs + odb digests equal). Prints the record after
    each leg so a watchdog kill salvages the finished ones."""
    import shutil
    import subprocess
    import sys
    import tempfile

    rows = int(os.environ.get("KART_BENCH_FLEET_ROWS", 100_000))
    n_replicas = int(os.environ.get("KART_BENCH_FLEET_REPLICAS", 2))
    n_tiles = int(os.environ.get("KART_BENCH_FLEET_TILE_COUNT", 48))
    zoom = int(os.environ.get("KART_BENCH_FLEET_ZOOM", 5))
    tile_clients = int(os.environ.get("KART_BENCH_FLEET_TILE_CLIENTS", 3))
    tile_reqs = int(os.environ.get("KART_BENCH_FLEET_TILE_REQUESTS", 500))
    clone_clients = int(os.environ.get("KART_BENCH_FLEET_CLONE_CLIENTS", 4))
    clone_reqs = int(os.environ.get("KART_BENCH_FLEET_CLONE_REQUESTS", 2))
    lag_pushes = int(os.environ.get("KART_BENCH_FLEET_LAG_PUSHES", 8))
    failover_commits = int(
        os.environ.get("KART_BENCH_FLEET_FAILOVER_COMMITS", 10)
    )
    poll_s = os.environ.get("KART_BENCH_FLEET_POLL_SECONDS", "0.3")

    from kart_tpu import transport
    from kart_tpu.synth import synth_repo

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=shm) as td:
        t0 = time.perf_counter()
        src, info = synth_repo(
            os.path.join(td, "primary"), rows, spatial=True, blobs="changed",
            edit_frac=0.01,
        )
        synth_s = time.perf_counter() - t0
        src.config["receive.denyCurrentBranch"] = "ignore"
        workdir = src.workdir or src.gitdir
        tile_oid = info["edit_commit"]

        record = {
            "metric": "fleet",
            "fleet_rows": rows,
            "fleet_replicas": n_replicas,
            "fleet_synth_seconds": round(synth_s, 2),
            "ok": True,
        }

        primary_port = _free_port()
        primary_url = f"http://127.0.0.1:{primary_port}/"
        serve_env = {"KART_TILE_MAX_FEATURES": "0"}
        primary = _spawn_serve(workdir, primary_port, serve_env)
        replica_urls = []
        replica_dirs = []
        replica_procs = []
        try:
            # -- spin up the replica fleet (env-configured, like any
            # -- production replica: KART_REPLICA_OF + the peer tier)
            from kart_tpu.core.repo import KartRepo

            t0 = time.perf_counter()
            for i in range(n_replicas):
                rdir = os.path.join(td, f"replica{i}")
                KartRepo.init_repository(rdir)
                port = _free_port()
                replica_procs.append(
                    _spawn_serve(
                        rdir, port,
                        {
                            **serve_env,
                            "KART_REPLICA_OF": primary_url,
                            "KART_PEER_CACHE": "primary",
                            "KART_REPLICA_POLL_SECONDS": poll_s,
                        },
                    )
                )
                replica_urls.append(f"http://127.0.0.1:{port}/")
                replica_dirs.append(rdir)
            want = _fleet_refs(primary_url)["heads"]
            deadline = time.monotonic() + 120
            for url in replica_urls:
                while _fleet_refs(url)["heads"] != want:
                    if time.monotonic() > deadline:
                        raise RuntimeError(f"replica {url} never converged")
                    time.sleep(0.1)
            record["fleet_initial_sync_seconds"] = round(
                time.perf_counter() - t0, 2
            )

            # -- leg 1: aggregate cached tiles/s across the fleet.
            # Warm: the primary encodes each sample tile once; each
            # replica then peer-fills it once — after this, every request
            # anywhere in the fleet is a cache memcpy, the steady state a
            # hot map layer serves from.
            from urllib.request import urlopen

            sample = _tile_sample(zoom, n_tiles, 7)
            for base in [primary_url] + replica_urls:
                for z, x, y in sample:
                    with urlopen(
                        f"{base}api/v1/tiles/{tile_oid}/synth/{z}/{x}/{y}"
                        f"?layers=bin",
                        timeout=120,
                    ) as resp:
                        resp.read()
            procs = []
            for i in range(n_replicas * tile_clients):
                url = replica_urls[i % n_replicas]
                p = subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--fleet-tile-worker", url, tile_oid, "synth",
                        str(tile_reqs), str(zoom), str(200 + i),
                    ],
                    env=_storm_env(),
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
                procs.append(p)  # _collect_workers reaps every worker
            go = _storm_go_barrier(procs)
            results = _collect_workers(procs)
            good = [r for r in results if r]
            ok_requests = sum(r.get("ok_requests", 0) for r in good)
            durations = sorted(d for r in good for d in r["durations"])
            record["fleet_tile_clients"] = n_replicas * tile_clients
            record["fleet_tile_requests_total"] = (
                n_replicas * tile_clients * tile_reqs
            )
            record["fleet_tile_ok_requests"] = ok_requests
            if go is not None and good:
                wall = max(r["end"] for r in good) - go
                record["fleet_agg_tiles_per_sec"] = round(
                    ok_requests / max(wall, 1e-9), 2
                )
                record["fleet_tile_p99_request_seconds"] = round(
                    durations[
                        min(len(durations) - 1, int(0.99 * len(durations)))
                    ],
                    4,
                )
            else:
                record["ok"] = False
                record["fleet_agg_tiles_per_sec"] = 0
                record["fleet_tile_p99_request_seconds"] = 0
            hits = misses = 0
            for url in replica_urls:
                doc = _fleet_stats_json(url)
                hits += _fleet_counter(doc, "fleet.peer_cache.hits")
                misses += _fleet_counter(doc, "fleet.peer_cache.misses")
            record["fleet_peer_cache_hit_rate"] = round(
                hits / max(1, hits + misses), 4
            )
            # the acceptance bar: a 2-replica fleet must beat the
            # single-node cached number (BENCH_r10 tiles_per_sec_cached)
            single_node = None
            r10 = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_r10.json"
            )
            if os.path.exists(r10):
                with open(r10) as f:
                    single_node = json.load(f).get("parsed", {}).get(
                        "tiles_per_sec_cached"
                    )
            if single_node:
                record["fleet_tiles_vs_single_node_cached"] = round(
                    record["fleet_agg_tiles_per_sec"] / single_node, 2
                )
                record["fleet_tiles_beats_single_node"] = (
                    record["fleet_agg_tiles_per_sec"] > single_node
                )
            record["ok"] = record["ok"] and ok_requests == (
                n_replicas * tile_clients * tile_reqs
            )
            print(json.dumps(record), flush=True)

            # -- leg 2: aggregate clone throughput fanned across replicas
            # (serve_storm's fetch worker, pointed at the fleet; the repo
            # is the columnar partial-clone state, so "features" ride as
            # sidecar columns, not per-feature blobs)
            procs = []
            for i in range(clone_clients):
                url = replica_urls[i % n_replicas]
                p = subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--serve-storm-worker", url,
                        os.path.join(td, "clones", f"w{i}"), str(clone_reqs),
                        "fetch",
                    ],
                    env=_storm_env(),
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
                procs.append(p)
            go = _storm_go_barrier(procs)
            results = _collect_workers(procs)
            good = [r for r in results if r and r.get("ok")]
            fetches = sum(len(r["durations"]) for r in good)
            record["fleet_clone_clients"] = clone_clients
            record["fleet_clone_ok"] = len(good) == clone_clients
            if go is not None and good:
                wall = max(r["end"] for r in good) - go
                record["fleet_agg_clone_features_per_sec"] = round(
                    rows * fetches / max(wall, 1e-9)
                )
            else:
                record["ok"] = False
                record["fleet_agg_clone_features_per_sec"] = 0
            record["ok"] = record["ok"] and record["fleet_clone_ok"]
            print(json.dumps(record), flush=True)

            # -- leg 3: replication lag, push-ack -> replica-visible.
            # Pushes go through replica 0 (the proxy kicks its sync loop);
            # replica 1 rides the poll — the honest spread of a real fleet.
            pusher = transport.clone(
                replica_urls[0], os.path.join(td, "pusher"),
                do_checkout=False,
            )
            pusher.config.set_many(
                {"user.name": "bench", "user.email": "bench@fleet"}
            )
            # only the synth edit rows carry real blobs in "changed" mode,
            # and a delete reads the old feature — mirror synth_repo's
            # edit-row selection (seed=0 ⇒ edit rng seed 1, pks offset by
            # the 1<<24 base) to pick deletable features
            rng = np.random.default_rng(1)
            edit_rows = rng.choice(
                rows, size=info["n_edits"], replace=False
            )
            pks = sorted((1 << 24) + int(r) for r in edit_rows)
            assert len(pks) >= lag_pushes + failover_commits
            from kart_tpu.synth import commit_feature_edits

            lag_samples = []
            for k in range(lag_pushes):
                oid = commit_feature_edits(
                    pusher, "synth", deletes=[pks[k]],
                    message=f"lag probe {k}",
                )
                transport.push(pusher, "origin")
                t_ack = time.monotonic()
                waiting = set(replica_urls)
                while waiting:
                    for url in sorted(waiting):
                        if _fleet_refs(url)["heads"].get("main") == oid:
                            lag_samples.append(time.monotonic() - t_ack)
                            waiting.discard(url)
                    if time.monotonic() - t_ack > 30:
                        record["ok"] = False
                        break
                    if waiting:
                        time.sleep(0.02)
            lag_samples.sort()
            record["fleet_lag_pushes"] = lag_pushes
            if lag_samples:
                record["fleet_replication_lag_p99_seconds"] = round(
                    lag_samples[
                        min(len(lag_samples) - 1,
                            int(0.99 * len(lag_samples)))
                    ],
                    4,
                )
                record["fleet_replication_lag_mean_seconds"] = round(
                    sum(lag_samples) / len(lag_samples), 4
                )
            else:
                record["ok"] = False
                record["fleet_replication_lag_p99_seconds"] = 0
                record["fleet_replication_lag_mean_seconds"] = 0
            print(json.dumps(record), flush=True)

            # -- leg 4: the failover drill. Writes keep flowing through a
            # replica proxy; the primary is SIGKILLed mid-storm and
            # restarted; every ACKED commit must survive on the primary
            # and reach every replica, and the replicas must converge
            # byte-identical.
            acked = []
            restarted = False
            for k in range(failover_commits):
                oid = commit_feature_edits(
                    pusher, "synth", deletes=[pks[lag_pushes + k]],
                    message=f"failover {k}",
                )
                if k == failover_commits // 2:
                    primary.kill()
                    primary.wait()
                deadline = time.monotonic() + 120
                while True:
                    try:
                        transport.push(pusher, "origin")
                        acked.append(oid)
                        break
                    except Exception as e:
                        if time.monotonic() > deadline:
                            record["ok"] = False
                            print(
                                f"failover push never landed: {e}",
                                file=sys.stderr,
                            )
                            break
                        if primary.poll() is not None and not restarted:
                            # the operator's restart: same store, same port
                            primary = _spawn_serve(
                                workdir, primary_port, serve_env
                            )
                            restarted = True
                        time.sleep(0.2)
            record["fleet_failover_commits_acked"] = len(acked)
            record["fleet_failover_restarted"] = restarted
            # wait for the whole fleet to converge on the final tip
            tip = _fleet_refs(primary_url)["heads"]["main"]
            deadline = time.monotonic() + 60
            for url in replica_urls:
                while _fleet_refs(url)["heads"].get("main") != tip:
                    if time.monotonic() > deadline:
                        record["ok"] = False
                        break
                    time.sleep(0.1)
            # zero lost landed commits: every acked oid is on disk on the
            # primary AND every replica
            lost = 0
            stores = [workdir] + replica_dirs
            opened = [KartRepo(p) for p in stores]
            for oid in acked:
                if not all(r.odb.contains(oid) for r in opened):
                    lost += 1
            record["fleet_failover_lost_commits"] = lost
            digests = [_fleet_store_digest(p) for p in replica_dirs]
            record["fleet_replicas_converged_identical"] = all(
                d == digests[0] for d in digests[1:]
            ) and digests[0][0] == dict(
                KartRepo(workdir).refs.iter_refs("refs/")
            )
            record["ok"] = (
                record["ok"]
                and lost == 0
                and len(acked) == failover_commits
                and record["fleet_replicas_converged_identical"]
            )
            print(json.dumps(record), flush=True)
        finally:
            for p in [primary] + replica_procs:
                try:
                    p.kill()
                    p.wait()
                except OSError:
                    pass
        shutil.rmtree(os.path.join(td, "clones"), ignore_errors=True)


# ---------------------------------------------------------------------------
# bench.py --live: K watchers × continuous pushes (ISSUE 14, docs/EVENTS.md §8)
# ---------------------------------------------------------------------------


def live_watch_worker():
    """One live-update watcher: subscribe to the primary's event feed and
    long-poll until ``n_events`` distinct events arrived (or the
    deadline). argv after the flag: ``url n_events``. Protocol as the
    other storm workers: ready / go / one JSON result line — the result
    maps each received sequence to its receive wall-clock, which the
    parent joins against its push-ack clocks for the invalidation fan-out
    latency."""
    import sys
    from urllib.request import urlopen

    i = sys.argv.index("--live-watch-worker")
    url, n_events = sys.argv[i + 1], int(sys.argv[i + 2])

    # the subscribe handshake (also creates the server-side emitter
    # before any push lands)
    with urlopen(f"{url}api/v1/events", timeout=60) as resp:
        since = json.loads(resp.read().decode())["head"]

    print(json.dumps({"ready": True}), flush=True)
    sys.stdin.readline()

    received = {}  # seq -> {"t": wall clock, "new": oid}
    deadline = time.time() + 300
    errors = []
    while len(received) < n_events and time.time() < deadline:
        try:
            with urlopen(
                f"{url}api/v1/events?since={since}&timeout=20", timeout=60
            ) as resp:
                doc = json.loads(resp.read().decode())
        except OSError as e:
            errors.append(str(e))
            time.sleep(0.2)
            continue
        now = time.time()
        for event in doc.get("events", ()):
            received.setdefault(
                int(event["seq"]), {"t": now, "new": event.get("new")}
            )
        since = max(since, int(doc.get("head", since)))
    print(
        json.dumps(
            {
                "ok": len(received) >= n_events,
                "received": {str(k): v for k, v in received.items()},
                "errors": errors[:5],
            }
        ),
        flush=True,
    )


def _live_event_exact(repo, event, margin=1):
    """Re-prove one event's dirty-tile exactness at bench scale: encode
    every candidate ``bin``-layer tile (the event bbox range ± margin,
    per zoom) at both commits and compare content — the computed set must
    equal the differing set, both directions. -> bool (None when the
    event carries no enumerated tiles to verify)."""
    import sys

    from kart_tpu import tiles
    from kart_tpu.tiles.encode import encode_tile, parse_payload
    from kart_tpu.tiles.grid import tile_range_for_bbox

    def content(oid, ds_path, z, x, y):
        source = tiles.source_for(repo, oid, ds_path)
        payload, _stats = encode_tile(
            source, z, x, y, layers=("bin",), max_features=0
        )
        header, layers = parse_payload(payload)
        header.pop("commit")
        return header, layers

    old_oid, new_oid = event.get("old"), event.get("new")
    dirty = event.get("dirty") or {}
    if not old_oid or not new_oid or not dirty:
        return None
    for ds_path, entry in dirty.items():
        if entry.get("tiles") is None or entry.get("bbox") is None:
            return None  # truncated / non-spatial: nothing exact to check
        for z in entry["zooms"]:
            n = 1 << z
            x0, y0, x1, y1 = tile_range_for_bbox(z, entry["bbox"])
            x0, y0 = max(0, x0 - margin), max(0, y0 - margin)
            x1, y1 = min(n - 1, x1 + margin), min(n - 1, y1 + margin)
            want = set()
            for x in range(x0, x1 + 1):
                for y in range(y0, y1 + 1):
                    if content(old_oid, ds_path, z, x, y) != content(
                        new_oid, ds_path, z, x, y
                    ):
                        want.add((x, y))
            got = {tuple(t) for t in entry["tiles"].get(str(z), [])}
            if got != want:
                print(
                    f"dirty-tile mismatch {ds_path} z{z}: cdc {sorted(got)}"
                    f" vs re-encode {sorted(want)}",
                    file=sys.stderr,
                )
                return False
    return True


def live_main():
    """`bench.py --live` (docs/EVENTS.md §8): K watchers hold long-polls
    against a serving primary while a pusher lands a stream of edit
    commits and one *subscribed* replica (poll interval cranked to 30s so
    the event stream, not the poll, drives it) syncs alongside. Legs:
    (1) invalidation fan-out latency push-ack → watcher-delivery, p99
    across K × pushes; (2) dirty-tile exactness re-proven per event vs a
    full re-encode; (3) post-announce requests for dirty tiles hit the
    pre-warmed cache; (4) the subscribed replica's replication lag p99 vs
    the polled BENCH_r13 number."""
    import shutil
    import subprocess
    import sys
    import tempfile
    from urllib.request import urlopen

    # r13's fleet scale, so the replica-lag comparison against its polled
    # number is apples-to-apples (same rows ⇒ same per-cycle sync cost;
    # the delta under test is event-kick vs poll-period)
    rows = int(os.environ.get("KART_BENCH_LIVE_ROWS", 100_000))
    n_watchers = int(os.environ.get("KART_BENCH_LIVE_WATCHERS", 6))
    n_pushes = int(os.environ.get("KART_BENCH_LIVE_PUSHES", 12))
    exact_events = int(os.environ.get("KART_BENCH_LIVE_EXACT_EVENTS", 4))

    from kart_tpu import transport
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.synth import commit_feature_edits, synth_repo

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=shm) as td:
        t0 = time.perf_counter()
        src, info = synth_repo(
            os.path.join(td, "primary"), rows, spatial=True,
            blobs="changed", edit_frac=0.01,
        )
        synth_s = time.perf_counter() - t0
        src.config["receive.denyCurrentBranch"] = "ignore"
        workdir = src.workdir or src.gitdir

        record = {
            "metric": "live",
            "live_rows": rows,
            "live_watchers": n_watchers,
            "live_pushes": n_pushes,
            "live_synth_seconds": round(synth_s, 2),
            "ok": True,
        }

        serve_env = {"KART_TILE_MAX_FEATURES": "0"}
        primary_port = _free_port()
        primary_url = f"http://127.0.0.1:{primary_port}/"
        primary = _spawn_serve(workdir, primary_port, serve_env)
        replica_dir = os.path.join(td, "replica")
        KartRepo.init_repository(replica_dir)
        replica_port = _free_port()
        replica_url = f"http://127.0.0.1:{replica_port}/"
        replica = _spawn_serve(
            replica_dir, replica_port,
            {
                **serve_env,
                "KART_REPLICA_OF": primary_url,
                # the poll must NOT be the thing that syncs: the event
                # subscription is under test
                "KART_REPLICA_POLL_SECONDS": "30",
            },
        )
        try:
            want = _fleet_refs(primary_url)["heads"]
            deadline = time.monotonic() + 180
            while _fleet_refs(replica_url)["heads"] != want:
                if time.monotonic() > deadline:
                    raise RuntimeError("replica never caught up initially")
                time.sleep(0.1)

            # -- watchers: subscribe, then go
            procs = []
            for i in range(n_watchers):
                p = subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--live-watch-worker", primary_url, str(n_pushes),
                    ],
                    env=_storm_env(),
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
                procs.append(p)
            go = _storm_go_barrier(procs)
            if go is None:
                raise RuntimeError("a watcher died before go")

            # -- the pusher: continuous single-commit pushes (deletes of
            # -- real-blob edit rows, synth_repo's deletable set)
            pusher = transport.clone(
                primary_url, os.path.join(td, "pusher"), do_checkout=False
            )
            pusher.config.set_many(
                {"user.name": "bench", "user.email": "bench@live"}
            )
            rng = np.random.default_rng(1)
            edit_rows = rng.choice(rows, size=info["n_edits"], replace=False)
            pks = sorted((1 << 24) + int(r) for r in edit_rows)
            assert len(pks) >= n_pushes

            acks = {}  # commit oid -> push-ack wall clock
            replica_lag = []
            head_seen = 0
            warm_requests = warm_hits = cold_encodes = 0
            for k in range(n_pushes):
                oid = commit_feature_edits(
                    pusher, "synth", deletes=[pks[k]],
                    message=f"live push {k}",
                )
                transport.push(pusher, "origin")
                acks[oid] = t_ack = time.time()
                # replica leg: event-kicked sync, 30s poll never fires
                mono0 = time.monotonic()
                while _fleet_refs(replica_url)["heads"].get("main") != oid:
                    if time.monotonic() - mono0 > 25:
                        record["ok"] = False
                        print(
                            f"replica missed push {k} inside 25s",
                            file=sys.stderr,
                        )
                        break
                    time.sleep(0.01)
                else:
                    replica_lag.append(time.time() - t_ack)
                # warm leg, the viewer protocol: on receipt of each
                # invalidation, re-fetch exactly its dirty tiles — they
                # must come from the pre-warmed cache (warm-then-announce
                # means the event's visibility implies its tiles are in;
                # stats deltas bracket the batch so only THESE requests
                # are counted)
                doc = json.loads(
                    urlopen(
                        f"{primary_url}api/v1/events"
                        f"?since={head_seen}&timeout=10",
                        timeout=30,
                    ).read().decode()
                )
                head_seen = max(head_seen, int(doc.get("head", head_seen)))
                pre = _fleet_stats_json(primary_url)
                batch = 0
                for event in doc.get("events", ()):
                    for ds_path, entry in (event.get("dirty") or {}).items():
                        for z_str, addrs in (entry.get("tiles") or {}).items():
                            for x, y in addrs:
                                with urlopen(
                                    f"{primary_url}api/v1/tiles/"
                                    f"{event['new']}/{ds_path}/"
                                    f"{z_str}/{x}/{y}?layers=bin",
                                    timeout=60,
                                ) as resp:
                                    resp.read()
                                batch += 1
                post = _fleet_stats_json(primary_url)
                warm_requests += batch
                warm_hits += _fleet_counter(
                    post, "tiles.cache.hits"
                ) - _fleet_counter(pre, "tiles.cache.hits")
                cold_encodes += _fleet_counter(
                    post, "tiles.cache.misses"
                ) - _fleet_counter(pre, "tiles.cache.misses")

            results = _collect_workers(procs)
            good = [r for r in results if r and r.get("ok")]
            record["live_watchers_served"] = len(good)
            record["ok"] = record["ok"] and len(good) == n_watchers

            # -- leg 1: invalidation fan-out latency (push-ack -> watcher)
            events_doc = json.loads(
                urlopen(
                    f"{primary_url}api/v1/events?since=0&timeout=0",
                    timeout=30,
                ).read().decode()
            )
            events = events_doc.get("events", [])
            record["live_events_total"] = events_doc.get("head", 0)
            fanout = []
            for r in good:
                for _seq, hit in r["received"].items():
                    t_ack = acks.get(hit.get("new"))
                    if t_ack is not None:
                        fanout.append(max(0.0, hit["t"] - t_ack))
            fanout.sort()
            if fanout:
                record["live_invalidation_p99_seconds"] = round(
                    fanout[min(len(fanout) - 1, int(0.99 * len(fanout)))], 4
                )
                record["live_invalidation_mean_seconds"] = round(
                    sum(fanout) / len(fanout), 4
                )
            else:
                record["ok"] = False
                record["live_invalidation_p99_seconds"] = 0
                record["live_invalidation_mean_seconds"] = 0
            print(json.dumps(record), flush=True)

            # -- leg 2: warm hit rate (accumulated per push above — the
            # warmer's own fills are misses by definition and happened
            # before each event's announcement, outside the brackets)
            record["live_warm_requests"] = warm_requests
            record["live_warm_hit_rate"] = round(
                warm_hits / max(1, warm_requests), 4
            )
            record["live_warm_cold_encodes"] = cold_encodes
            print(json.dumps(record), flush=True)

            # -- leg 3: dirty-tile exactness vs a full re-encode, on the
            # primary's own store (sampled events; every zoom)
            bench_repo = KartRepo(workdir)
            verdicts = [
                _live_event_exact(bench_repo, event)
                for event in events[:exact_events]
            ]
            checked = [v for v in verdicts if v is not None]
            record["live_dirty_tiles_exact_events"] = len(checked)
            record["live_dirty_tiles_exact"] = bool(checked) and all(checked)
            record["ok"] = record["ok"] and record["live_dirty_tiles_exact"]
            print(json.dumps(record), flush=True)

            # -- leg 4: subscribed-replica lag vs the polled BENCH_r13
            replica_lag.sort()
            if replica_lag:
                record["live_replica_lag_p99_seconds"] = round(
                    replica_lag[
                        min(len(replica_lag) - 1,
                            int(0.99 * len(replica_lag)))
                    ],
                    4,
                )
                record["live_replica_lag_mean_seconds"] = round(
                    sum(replica_lag) / len(replica_lag), 4
                )
            else:
                record["ok"] = False
                record["live_replica_lag_p99_seconds"] = 0
                record["live_replica_lag_mean_seconds"] = 0
            r13 = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "BENCH_r13.json"
            )
            polled = None
            if os.path.exists(r13):
                with open(r13) as f:
                    polled = json.load(f).get("parsed", {}).get(
                        "fleet_replication_lag_p99_seconds"
                    )
            if polled:
                record["live_replica_lag_vs_polled_p99"] = round(
                    record["live_replica_lag_p99_seconds"] / polled, 3
                )
                record["live_replica_lag_beats_polled"] = (
                    0
                    < record["live_replica_lag_p99_seconds"]
                    < polled
                )
            print(json.dumps(record), flush=True)
        finally:
            for p in (primary, replica):
                try:
                    p.kill()
                    p.wait()
                except OSError:
                    pass
        shutil.rmtree(os.path.join(td, "pusher"), ignore_errors=True)


def query_main():
    """`bench.py --query` (docs/QUERY.md §6): the ISSUE 16 query engine.
    Legs: (1) predicate-pushdown scan — a selective bbox over a spatial
    synth repo with block pruning on vs forced off (KART_BLOCK_PRUNE=0),
    identical counts required, prune fraction recorded against the >=95%
    bar; (2) the headline spatial join at 100M probe x 1M build envelope
    rows, host_native vs the sharded device backend, exact per-count
    cross-validation; (3) the same join scattered across 2 replicas of a
    shared store vs a single node. Prints the record after each leg so a
    watchdog kill salvages the finished ones."""
    import tempfile
    import threading
    from urllib.request import urlopen

    import numpy as np

    scan_rows = int(os.environ.get("KART_BENCH_QUERY_SCAN_ROWS", 10_000_000))
    probe_rows = int(os.environ.get("KART_BENCH_QUERY_ROWS", 100_000_000))
    build_rows = int(
        os.environ.get("KART_BENCH_QUERY_BUILD_ROWS", 1_000_000)
    )
    scatter_rows = int(
        os.environ.get("KART_BENCH_QUERY_SCATTER_ROWS", 4_000_000)
    )

    from kart_tpu.query import run_query
    from kart_tpu.synth import synth_envelopes, synth_repo
    from kart_tpu.transport.http import make_server

    record = {
        "metric": "query",
        "query_scan_rows": scan_rows,
        "query_join_probe_rows": probe_rows,
        "query_join_build_rows": build_rows,
        "query_scatter_rows": scatter_rows,
        "ok": True,
    }

    def _clear_query_caches():
        from kart_tpu.query import cache as qcache

        with qcache._query_caches_lock:
            qcache._QUERY_CACHES.clear()

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    pk0 = 1 << 24

    # -- leg 1: the pushdown scan, pruned vs unpruned ---------------------
    with tempfile.TemporaryDirectory(dir=shm) as td:
        t0 = time.perf_counter()
        repo, info = synth_repo(
            os.path.join(td, "scan"), scan_rows, spatial=True,
            blobs="promised",
        )
        record["query_scan_synth_seconds"] = round(
            time.perf_counter() - t0, 2
        )
        base = info["base_commit"]
        from kart_tpu.diff import sidecar

        block = sidecar.ensure_block(
            repo, repo.datasets(base)["synth"], pad=False
        )
        env = np.asarray(block.envelopes[: 1 << 16], dtype=np.float64)
        w = float(env[:, 0].min())
        # ~1% of the longitude span: selective enough that a pruned scan
        # should skip >=95% of blocks outright
        bbox = (
            f"{w},{float(env[:, 1].min())},"
            f"{w + (float(env[:, 2].max()) - w) * 0.01},"
            f"{float(env[:, 3].max())}"
        )
        del block, env

        run_query(repo, base, "synth", bbox=bbox)  # warm: mmap page-in
        t0 = time.perf_counter()
        pruned = run_query(repo, base, "synth", bbox=bbox)
        pruned_s = time.perf_counter() - t0
        os.environ["KART_BLOCK_PRUNE"] = "0"
        try:
            run_query(repo, base, "synth", bbox=bbox)  # warm full-scan pages
            t0 = time.perf_counter()
            unpruned = run_query(repo, base, "synth", bbox=bbox)
            unpruned_s = time.perf_counter() - t0
        finally:
            del os.environ["KART_BLOCK_PRUNE"]
        stats = pruned["stats"]
        record["query_scan_seconds"] = round(pruned_s, 4)
        record["query_scan_rows_per_sec"] = round(scan_rows / pruned_s)
        record["query_scan_unpruned_seconds"] = round(unpruned_s, 4)
        record["query_scan_rows_per_sec_unpruned"] = round(
            scan_rows / unpruned_s
        )
        record["query_scan_matches"] = pruned["count"]
        record["query_scan_pruned_matches_unpruned"] = (
            pruned["count"] == unpruned["count"]
        )
        prune_frac = stats["blocks_pruned"] / max(stats["blocks"], 1)
        record["query_scan_block_prune_fraction"] = round(prune_frac, 4)
        record["query_scan_prune_meets_95pct"] = prune_frac >= 0.95
        record["query_scan_prune_speedup"] = round(unpruned_s / pruned_s, 2)

        # exact vs approx (docs/QUERY.md §4b): the pruned leg above ran
        # the default exact-refine semantics; re-run with --approx to
        # price the refine stage. Synth geometry IS its envelope (box
        # polygons), so the counts must agree exactly.
        run_query(repo, base, "synth", bbox=bbox, approx=True)  # warm
        t0 = time.perf_counter()
        approx = run_query(repo, base, "synth", bbox=bbox, approx=True)
        approx_s = time.perf_counter() - t0
        record["query_scan_approx_seconds"] = round(approx_s, 4)
        record["query_scan_refine_pairs"] = stats["pairs_refined"]
        record["query_scan_refine_overhead"] = round(
            pruned_s / max(approx_s, 1e-9), 2
        )
        record["query_scan_exact_matches_approx"] = (
            pruned["count"] == approx["count"]
        )
        print(json.dumps(record), flush=True)

    # -- leg 2: the headline join kernel, host vs device ------------------
    # Envelope columns straight from the synth generator: the join never
    # touches blobs, so this measures exactly what the repo-level path
    # measures minus one mmap — at 100M x 1M only pruning makes any
    # backend feasible, which is the point of the staged kernel.
    from kart_tpu.diff.sidecar import AGG_BLOCK_ROWS, _block_aggregates
    from kart_tpu.query.join import join_counts_for_range

    probe_env = synth_envelopes(np.arange(pk0, pk0 + probe_rows))
    build_env = synth_envelopes(np.arange(pk0, pk0 + build_rows))

    class _Probe:
        envelopes = probe_env
        env_blocks = (*_block_aggregates(probe_env, AGG_BLOCK_ROWS),
                      AGG_BLOCK_ROWS)
        count = probe_rows

    cand_pairs = probe_rows * build_rows
    t0 = time.perf_counter()
    host_counts, host_total = join_counts_for_range(
        build_env, _Probe, 0, probe_rows, allow_device=False
    )
    host_s = time.perf_counter() - t0
    record["query_join_pairs"] = int(host_total)
    record["query_join_host_seconds"] = round(host_s, 3)
    record["query_join_pairs_per_sec_100m_x_1m_host"] = round(
        cand_pairs / host_s
    )

    os.environ["KART_DIFF_SHARDED"] = "1"
    try:
        t0 = time.perf_counter()
        dev_counts, dev_total = join_counts_for_range(
            build_env, _Probe, 0, probe_rows, allow_device=True,
            route_rows=probe_rows,
        )
        dev_s = time.perf_counter() - t0
    finally:
        del os.environ["KART_DIFF_SHARDED"]
    record["query_join_device_seconds"] = round(dev_s, 3)
    record["query_join_pairs_per_sec_100m_x_1m"] = round(cand_pairs / dev_s)
    record["query_join_device_vs_host"] = round(host_s / dev_s, 2)
    record["query_join_device_matches_host"] = bool(
        np.array_equal(host_counts, dev_counts) and host_total == dev_total
    )
    del probe_env, build_env, host_counts, dev_counts, _Probe
    print(json.dumps(record), flush=True)

    # -- leg 2b: the exact-refine kernel, bbox-only vs host vs device -----
    # Candidate pairs of quantized box polygons through the refine seam
    # (docs/DEVICE.md §6): the envelope overlap every pair already passed
    # is the baseline the exact predicates are priced against; host and
    # device verdicts must be bit-identical.
    from kart_tpu.diff.backend import refine_intersects
    from kart_tpu.geom import VertexColumn, refine_pairs_host

    refine_pairs = int(
        os.environ.get("KART_BENCH_REFINE_PAIRS", 2_000_000)
    )
    refine_feats = 1 << 14

    def _box_col(seed):
        rng = np.random.default_rng(seed)
        cx = rng.integers(-170, 170, refine_feats) * 100_000
        cy = rng.integers(-80, 80, refine_feats) * 100_000
        w = rng.integers(1_000, 200_000, refine_feats)
        h = rng.integers(1_000, 200_000, refine_feats)
        x = np.stack([cx - w, cx + w, cx + w, cx - w], 1).ravel()
        y = np.stack([cy - h, cy - h, cy + h, cy + h], 1).ravel()
        n = refine_feats
        col = VertexColumn(
            np.full(n, 3, np.uint8),
            np.arange(n + 1, dtype=np.int64),
            np.arange(n + 1, dtype=np.int64) * 4,
            x.astype(np.int32),
            y.astype(np.int32),
        )
        env = np.stack([cx - w, cy - h, cx + w, cy + h], 1)
        return col, env

    (col_a, box_a), (col_b, box_b) = _box_col(1), _box_col(2)
    rng = np.random.default_rng(3)
    ia = rng.integers(0, refine_feats, refine_pairs).astype(np.int64)
    ib = rng.integers(0, refine_feats, refine_pairs).astype(np.int64)
    t0 = time.perf_counter()
    ea, eb = box_a[ia], box_b[ib]
    bbox_hits = ~(
        (ea[:, 2] < eb[:, 0]) | (eb[:, 2] < ea[:, 0])
        | (ea[:, 3] < eb[:, 1]) | (eb[:, 3] < ea[:, 1])
    )
    bbox_s = time.perf_counter() - t0
    record["query_refine_pairs"] = refine_pairs
    record["query_refine_pairs_per_sec_bbox_only"] = round(
        refine_pairs / bbox_s
    )

    t0 = time.perf_counter()
    host_v = refine_pairs_host(col_a, ia, col_b, ib)
    host_s = time.perf_counter() - t0
    record["query_refine_matches"] = int(np.count_nonzero(host_v))
    record["query_refine_pairs_per_sec_host"] = round(refine_pairs / host_s)
    record["query_refine_exact_vs_bbox_cost"] = round(host_s / bbox_s, 1)

    os.environ["KART_DIFF_SHARDED"] = "1"
    try:
        refine_intersects(  # warm: compile the fixed-shape kernel
            col_a, ia[:4096], col_b, ib[:4096], route_rows=refine_pairs
        )
        t0 = time.perf_counter()
        dev_v = refine_intersects(
            col_a, ia, col_b, ib, route_rows=refine_pairs
        )
        dev_s = time.perf_counter() - t0
    finally:
        del os.environ["KART_DIFF_SHARDED"]
    record["query_refine_pairs_per_sec_device"] = round(refine_pairs / dev_s)
    record["query_refine_device_vs_host"] = round(host_s / dev_s, 2)
    record["query_refine_device_matches_host"] = bool(
        np.array_equal(host_v, dev_v)
    )
    del col_a, col_b, ia, ib, host_v, dev_v, box_a, box_b
    print(json.dumps(record), flush=True)

    # -- leg 3: the 2-replica scatter vs a single node --------------------
    # Shared-store fleet shape: one peer `kart serve` process answers the
    # upper probe half as a commit-addressed partial while this process's
    # node computes the lower half — wall clock vs the same join on one
    # node, exact counts required.
    with tempfile.TemporaryDirectory(dir=shm) as td:
        t0 = time.perf_counter()
        repo, info = synth_repo(
            os.path.join(td, "scatter"), scatter_rows, spatial=True,
            blobs="changed",
        )
        record["query_scatter_synth_seconds"] = round(
            time.perf_counter() - t0, 2
        )
        base, edit = info["base_commit"], info["edit_commit"]
        workdir = repo.workdir or repo.gitdir

        from kart_tpu import fleet as fleet_mod

        peer_port = _free_port()
        peer = _spawn_serve(workdir, peer_port)
        single_server = make_server(repo)
        threading.Thread(
            target=single_server.serve_forever, daemon=True
        ).start()
        node = fleet_mod.FleetNode(
            repo, primary_url=None,
            peers=(f"http://127.0.0.1:{peer_port}/",),
        )
        scatter_server = make_server(repo, fleet=node)
        threading.Thread(
            target=scatter_server.serve_forever, daemon=True
        ).start()
        try:
            path = (
                f"/api/v1/query?ref={base}&dataset=synth"
                f"&intersects={edit}:synth"
            )
            deadline = time.monotonic() + 60
            while True:  # wait for the peer process to accept
                try:
                    with urlopen(
                        f"http://127.0.0.1:{peer_port}/api/v1/stats",
                        timeout=5,
                    ):
                        break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.2)

            single_url = (
                f"http://127.0.0.1:{single_server.server_address[1]}"
            )
            t0 = time.perf_counter()
            with urlopen(single_url + path, timeout=3600) as resp:
                single_doc = json.loads(resp.read())
            single_s = time.perf_counter() - t0

            _clear_query_caches()  # the single-node doc must not be reused
            scatter_url = (
                f"http://127.0.0.1:{scatter_server.server_address[1]}"
            )
            t0 = time.perf_counter()
            with urlopen(scatter_url + path, timeout=3600) as resp:
                scatter_doc = json.loads(resp.read())
            scatter_s = time.perf_counter() - t0

            sc_pairs = scatter_rows * scatter_rows
            record["query_join_single_node_seconds"] = round(single_s, 3)
            record["query_join_scatter2_seconds"] = round(scatter_s, 3)
            record["query_join_pairs_per_sec_100m_x_1m_scatter2"] = round(
                sc_pairs / scatter_s
            )
            record["query_scatter_speedup"] = round(single_s / scatter_s, 2)
            record["query_scatter_matches_single"] = (
                scatter_doc["pairs"] == single_doc["pairs"]
                and scatter_doc["count"] == single_doc["count"]
            )
            record["query_scatter_parts"] = scatter_doc["stats"].get(
                "scatter_parts", 0
            )
        finally:
            single_server.shutdown()
            single_server.server_close()
            scatter_server.shutdown()
            scatter_server.server_close()
            try:
                peer.kill()
                peer.wait()
            except OSError:
                pass
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    import sys

    if "--tiles-storm-worker" in sys.argv:
        tiles_storm_worker()
    elif "--tiles" in sys.argv:
        tiles_main()
    elif "--live-watch-worker" in sys.argv:
        live_watch_worker()
    elif "--live" in sys.argv:
        live_main()
    elif "--fleet-tile-worker" in sys.argv:
        fleet_tile_worker()
    elif "--fleet" in sys.argv:
        fleet_main()
    elif "--merge-storm-worker" in sys.argv:
        merge_storm_worker()
    elif "--merge-storm" in sys.argv:
        merge_storm_main()
    elif "--serve-storm-worker" in sys.argv:
        serve_storm_worker()
    elif "--serve-storm" in sys.argv:
        serve_storm_main()
    elif "--query" in sys.argv:
        query_main()
    elif "--multichip-worker" in sys.argv:
        multichip_worker()
    elif "--multichip" in sys.argv:
        multichip_main()
    elif "--worker" in sys.argv:
        worker()
    else:
        main()
