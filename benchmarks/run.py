#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json, in one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name BENCHMARK.json gives it:
``configs/<config>.json`` (and the layer builder it names under ``layers/``),
``traffic/<traffic>.json`` (and the op kind and reference it names under
``ops/`` and ``references/``), ``metrics/<metric>.json`` (and the reader it
names under ``readers/``). A new cell, configuration or metric is new files
and new entries; no file here is edited for it.

One run, in order: ``import jax``, and no result unless it finds a TPU and
as many chips as the cell asks for -> the layer (base from
``.cache/layers/`` in this directory, built there when absent; the edit
commit made anew from ``--seed``) -> the first command with spans on: which
engine answered, fallbacks, the reference, the host twin -> ``setup_s`` ->
the window: the command repeated by one waiting client until ``--seconds``
have passed (``--trace 1``: spans on and the profiler around at most five
commands or ten seconds, and the window ends there) -> no compile inside the
window -> the last line of stdout, one JSON object.

``--rows`` is for rehearsals on the CPU only: it shrinks the layer, lets the
run go on without a TPU, and such a run always ends ``correct: false`` with
a non-zero exit code.
"""

import time

T0 = time.perf_counter()  # before any heavy import: setup_s starts here

import argparse
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_MAX_OPS = 5
TRACE_MAX_SECONDS = 10.0


def progress(msg):
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rows", type=int, default=None,
                   help="rehearsal only: rows of the layer; the run ends "
                   "correct: false")
    p.add_argument("--cache-dir", default=os.path.join(HERE, ".cache"),
                   help="where built layers are kept (rehearsals and tests "
                   "point it elsewhere)")
    return p.parse_args(argv)


def find_cell(manifest, name):
    cells = [w for w in manifest["workloads"] if w["name"] == name]
    if len(cells) != 1:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[0]
    (config_entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    return cell, load_json(ROOT, config_entry["file"])


def reported(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class CompileLog:
    """What jax compiled, or loaded from its persistent cache, heard through
    jax.monitoring (as chip_smoke.CompileLog): seconds and program names."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = []

    def on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs.append(str(kwargs.get("fun_name")))


def base_layer(name, config, cache_dir):
    """The configuration's base layer directory, built when absent. Its
    name carries a hash of the builder's source and parameters, so a changed
    builder never reads another's layer. -> (builder module, directory)."""
    layer = config["layer"]
    builder = load_module("layers", layer["builder"])
    with open(builder.__file__, "rb") as f:
        key = hashlib.sha256(
            f.read() + json.dumps(layer["params"], sort_keys=True).encode()
        ).hexdigest()[:16]
    path = os.path.join(cache_dir, "layers", f"{name}-{key}")
    if not os.path.exists(os.path.join(path, "base.json")):
        progress(f"layer: building {path}")
        building = path + ".building"
        shutil.rmtree(building, ignore_errors=True)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(building)
        builder.build_base(building, layer["params"])
        os.rename(building, path)
    return builder, path


def device_report(jax):
    devices = jax.devices()
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def run(args, work):
    import reduce

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell, config = find_cell(manifest, args.workload)
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    rehearsal = args.rows is not None
    if rehearsal:
        config["layer"]["params"]["rows"] = args.rows
    checks = {"not_a_rehearsal": not rehearsal}
    clock = {}

    # -- the device -----------------------------------------------------------
    # a persisted probe verdict must not answer for the chip (as chip_smoke)
    os.environ["KART_PROBE_CACHE"] = "0"
    t = time.perf_counter()
    import jax

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles.on_duration)
    devices = jax.devices()
    clock["backend_s"] = time.perf_counter() - t
    checks["platform_is_tpu"] = devices[0].platform == "tpu"
    checks["device_count"] = len(devices) == cell["chips"]
    if not rehearsal and not (checks["platform_is_tpu"] and checks["device_count"]):
        progress(
            f"found {len(devices)} x {devices[0].platform}, the cell asks for "
            f"{cell['chips']} x tpu: no result"
        )
        return 3

    # -- the layer ------------------------------------------------------------
    t = time.perf_counter()
    builder, base = base_layer(cell["config"], config, args.cache_dir)
    repo_path, info = builder.add_edit_commit(
        base, work, config["layer"]["params"], args.seed
    )
    clock["layer_s"] = time.perf_counter() - t
    counted = {k: v for k, v in info.items() if k.startswith("n_") and isinstance(v, int)}
    progress(f"layer: ready ({clock['layer_s']:.1f} s), {counted}")

    # -- the first command: route, fallbacks, reference, twin -----------------
    op = load_module("ops", traffic["op"]).Op(traffic, repo_path, work)
    checks["auto_routing"] = not any(k in os.environ for k in op.ROUTING_OVERRIDES)
    reference = load_module("references", traffic["reference"])
    op.spans(True)
    t = time.perf_counter()
    code, stdout = op.run()
    clock["first_diff_s"] = time.perf_counter() - t
    backends = reduce.span_attrs(op.take_spans(), traffic["backend_span"], "backend")
    verified = op.output(stdout)
    digest = hashlib.sha256(verified).digest()
    want = config["expect_backend"][str(cell["chips"])]
    checks["first_exit_0"] = code == 0
    checks["backend"] = backends == [want]
    checks.update(reference.check(verified, info))
    code, stdout = op.run(env=op.HOST_TWIN_ENV)
    twin_backends = reduce.span_attrs(
        op.take_spans(), traffic["backend_span"], "backend"
    )
    checks["twin_is_host"] = twin_backends == ["host_native"]
    checks["equals_twin"] = code == 0 and op.output(stdout) == verified
    progress(f"first command {clock['first_diff_s']:.2f} s on {backends}; "
             f"twin on {twin_backends}; checks {checks}")
    clock["compile_s"] = compiles.seconds
    clock["setup_s"] = time.perf_counter() - T0

    # -- the window -----------------------------------------------------------
    traced = bool(args.trace)
    op.spans(traced)
    trace_dir = os.path.join(work, "profile")
    walls, ops_events, failed = [], [], 0
    compiled_before = len(compiles.programs)
    if traced:
        # the device tracer is what is read; the Python tracer only slows
        # the host whose share of the command is being measured
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    window_t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        code, stdout = op.run()
        walls.append(time.perf_counter() - t)
        out = op.output(stdout) if code == 0 else b""
        good = len(out) == len(verified) and hashlib.sha256(out).digest() == digest
        failed += not good
        elapsed = time.perf_counter() - window_t0
        if traced:
            events = op.take_spans()
            ops_events.append(events)
            checks["backend"] = checks["backend"] and reduce.span_attrs(
                events, traffic["backend_span"], "backend") == [want]
            if len(walls) >= TRACE_MAX_OPS or elapsed >= min(
                TRACE_MAX_SECONDS, args.seconds
            ):
                break
        elif elapsed >= args.seconds and len(walls) >= traffic["min_ops"]:
            break
    if traced:
        jax.profiler.stop_trace()
    op.spans(False)
    in_window = compiles.programs[compiled_before:]
    checks["no_compile_in_window"] = not in_window
    checks["no_fallbacks"] = op.fallbacks() == 0
    progress(f"window: {len(walls)} commands, {failed} failed, walls "
             f"{[round(w, 4) for w in walls[:40]]}, compiled {in_window}")

    # -- the result -----------------------------------------------------------
    result = {
        "correct": all(checks.values()) and failed == 0,
        "attempted": len(walls),
        "failed": failed,
        "metrics": {},
        "device": device_report(jax),
        "checks": checks,
    }
    xla = reduce.read_xplane(trace_dir) if traced else []
    ctx = {
        "clock": clock, "ops_events": ops_events, "ops_walls": walls,
        "xla": xla, "device_kind": result["device"]["kind"],
    }
    for metric in manifest["per_layer" if traced else "end_to_end"]:
        if not reported(metric, cell["name"]):
            continue
        spec = load_json(HERE, "metrics", metric["name"] + ".json")
        value = load_module("readers", spec["reader"]).read(ctx, **spec["args"])
        if value is not None:
            result["metrics"][metric["name"]] = {
                "value": value, "unit": metric["unit"]
            }
    if traced:
        busy = reduce.device_busy_seconds(xla)
        if busy is not None:
            result["device"]["busy_s"] = busy
        result["device"]["window_s"] = sum(walls)
        result["breakdown"] = {
            "device_ops": reduce.top_device_ops(xla),
            "idle_gaps": reduce.host_side_seconds(
                ops_events, walls, busy, traffic["backend_span"]
            ),
        }
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def main(argv=None):
    args = parse_args(argv)
    sys.path.insert(0, ROOT)  # the system under test: kart_tpu
    sys.path.insert(0, HERE)  # reduce, costs
    work = tempfile.mkdtemp(prefix="kart-bench-")
    try:
        return run(args, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
