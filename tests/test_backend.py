"""DiffBackend registry + probe-verdict cache (ISSUE 6).

Also the tier-1 multi-device CI leg: the suite always runs on the 8-device
virtual CPU platform (conftest), and the CLI test below forces
KART_DIFF_BACKEND=sharded_jax so the shard_map path is exercised end-to-end
on every test run, TPU hardware or not."""

import json
import os

import numpy as np
import pytest

import jax

import kart_tpu.runtime as runtime
from kart_tpu.diff.backend import (
    BACKENDS,
    sampled_counts_pmapped,
    select_backend,
    sharded_envelope_hits,
)
from kart_tpu.ops.blocks import FeatureBlock
from kart_tpu.ops.diff_kernel import classify_blocks_host


def _pair(n=4000, seed=23):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(10 * n, size=n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    old = FeatureBlock.from_arrays(keys.copy(), oids.copy(), [f"f/{k}" for k in keys])
    no = oids.copy()
    no[::41] = rng.integers(0, 2**32, size=(len(no[::41]), 5), dtype=np.uint32)
    new = FeatureBlock.from_arrays(keys.copy(), no, [f"f/{k}" for k in keys])
    return old, new


# --- registry / selection ----------------------------------------------------

def test_registry_names():
    assert set(BACKENDS) == {"host_native", "device_jax", "sharded_jax"}


def test_env_forces_backend(monkeypatch):
    for name in BACKENDS:
        monkeypatch.setenv("KART_DIFF_BACKEND", name)
        assert select_backend(10**9).name == name
        assert select_backend(1).name == name


def test_unknown_backend_falls_back_to_auto(monkeypatch):
    monkeypatch.setenv("KART_DIFF_BACKEND", "warp_drive")
    assert select_backend(100).name == "host_native"  # tiny -> host


def test_auto_small_blocks_stay_host(monkeypatch):
    monkeypatch.setenv("KART_DIFF_BACKEND", "auto")
    monkeypatch.setenv("KART_DIFF_SHARDED", "auto")
    monkeypatch.setenv("KART_DIFF_DEVICE", "auto")
    assert select_backend(1000).name == "host_native"


def test_auto_forced_sharding_routes_to_sharded(monkeypatch):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    monkeypatch.setenv("KART_DIFF_SHARDED", "1")
    assert select_backend(10).name == "sharded_jax"


def test_every_backend_classifies_identically(monkeypatch):
    old, new = _pair()
    want = classify_blocks_host(old, new)
    for name, backend in BACKENDS.items():
        got = backend.classify(old, new)
        assert got[2] == want[2], name
        np.testing.assert_array_equal(got[0], want[0], err_msg=name)
        np.testing.assert_array_equal(got[1], want[1], err_msg=name)
        assert backend.counts(old, new) == want[2], name


def test_sharded_sampled_counts_match_host():
    old, new = _pair(seed=5)
    want = classify_blocks_host(old, new)[2]
    assert sampled_counts_pmapped(old, new) == want
    assert BACKENDS["sharded_jax"].sampled_counts(old, new) == want


# --- sharded envelope prefilter ---------------------------------------------

def test_sharded_envelope_hits_bit_identical_to_native():
    from kart_tpu.native import bbox_intersects_f32

    rng = np.random.default_rng(31)
    n = 20_000
    w = rng.uniform(-180, 179, n).astype(np.float32)
    e = np.minimum(w + rng.uniform(0, 8, n).astype(np.float32), 180)
    s = rng.uniform(-90, 88, n).astype(np.float32)
    nn = np.minimum(s + rng.uniform(0, 8, n).astype(np.float32), 90)
    envs = np.stack([w, s, e, nn], axis=1)
    wrap = rng.choice(n, 300, replace=False)  # anti-meridian envelopes
    envs[wrap, 0], envs[wrap, 2] = envs[wrap, 2].copy(), envs[wrap, 0].copy()
    for query in (
        (-20.25, -15.5, 44.875, 30.125),
        (0.1, 0.2, 0.3, 0.4),          # tiny rect
        (-180.0, -90.0, 180.0, 90.0),  # whole world
        (10.000001, -5.0, 10.000002, 5.0),  # f32-rounding edge
    ):
        q = np.asarray(query, dtype=np.float64)
        want = np.asarray(bbox_intersects_f32(envs, q))
        got = sharded_envelope_hits(envs, n, q)
        np.testing.assert_array_equal(got, want, err_msg=str(query))


def test_wrapping_query_uses_host_path(monkeypatch):
    """A wrapping filter rectangle must take the host engine's exact cyclic
    math (the device kernel only mirrors the non-wrapping branchless scan)."""
    rng = np.random.default_rng(2)
    n = 100
    envs = np.stack(
        [
            rng.uniform(-180, 170, n),
            rng.uniform(-90, 80, n),
            rng.uniform(-180, 180, n),
            rng.uniform(-80, 90, n),
        ],
        axis=1,
    ).astype(np.float32)
    block = FeatureBlock(
        np.arange(n, dtype=np.int64),
        np.zeros((n, 5), dtype=np.uint32),
        None,
        n,
        envelopes=envs,
    )
    query = np.asarray((170.0, -10.0, -170.0, 10.0))  # qe < qw: wraps
    from kart_tpu.native import bbox_intersects_f32

    got = BACKENDS["sharded_jax"].envelope_hits(block, query)
    np.testing.assert_array_equal(got, np.asarray(bbox_intersects_f32(envs, query)))


# --- tier-1 multi-device CI leg ---------------------------------------------

def test_cli_diff_through_sharded_backend(tmp_path, monkeypatch):
    """A real `kart diff` (repo + sidecars) with the sharded backend forced
    runs the shard_map record-batch path on the virtual mesh and produces
    output identical to the host engine — the multi-device leg every tier-1
    run exercises without TPU hardware."""
    from helpers import make_repo_with_edits

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from click.testing import CliRunner

    from kart_tpu.cli import cli
    from kart_tpu.parallel.sharded_diff import STATS

    repo_path, expected = make_repo_with_edits(tmp_path)
    monkeypatch.setenv("KART_DIFF_ENGINE", "columnar")

    monkeypatch.setenv("KART_DIFF_BACKEND", "host_native")
    host = CliRunner().invoke(
        cli, ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json"],
        catch_exceptions=False,
    )
    assert host.exit_code == 0, host.output

    monkeypatch.setenv("KART_DIFF_BACKEND", "sharded_jax")
    before = STATS["sharded_classify_calls"]
    sharded = CliRunner().invoke(
        cli, ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json"],
        catch_exceptions=False,
    )
    assert sharded.exit_code == 0, sharded.output
    assert STATS["sharded_classify_calls"] > before, (
        "diff completed without the sharded record-batch classify"
    )
    assert sharded.output == host.output  # byte-identical CLI output
    diff = json.loads(sharded.output)["kart.diff/v1+hexwkb"]
    ds = diff[next(iter(diff))]
    assert len(ds["feature"]) == sum(expected.values())


# --- probe verdict cache -----------------------------------------------------

@pytest.fixture
def probe_cache(tmp_path, monkeypatch):
    path = tmp_path / "probe.json"
    monkeypatch.setenv("KART_PROBE_CACHE", str(path))
    monkeypatch.setattr(runtime, "_probe_result", None)
    monkeypatch.setattr(runtime, "_probe_thread", None)
    monkeypatch.setattr(runtime, "_probe_box", None)
    return path


def test_probe_verdict_persisted_and_reused(probe_cache, monkeypatch):
    info = runtime.probe_backend()
    assert info["ok"] and not info.get("cached")
    assert probe_cache.exists()
    saved = json.loads(probe_cache.read_text())
    (key,) = saved.keys()
    assert "jax=" in key and "machine=" in key and "timeout=" in key
    # fresh process simulation: the verdict is adopted from the file
    monkeypatch.setattr(runtime, "_probe_result", None)
    monkeypatch.setattr(runtime, "_probe_thread", None)
    monkeypatch.setattr(runtime, "_probe_box", None)
    info2 = runtime.probe_backend()
    assert info2["ok"] and info2.get("cached") is True


def test_cached_failure_is_a_choice_not_a_timeout(probe_cache, monkeypatch):
    """The BENCH_r05 wound: a timed-out probe must cost later processes
    nothing. A persisted failure verdict is adopted instantly."""
    import time

    key = runtime._probe_cache_key(runtime._resolve_timeout(None))
    runtime._store_verdict(key, runtime._failure("backend init timed out after 75s", 75))
    t0 = time.perf_counter()
    info = runtime.probe_backend()
    assert time.perf_counter() - t0 < 5  # microseconds, not a 75s re-probe
    assert not info["ok"] and info.get("cached") is True


def test_reprobe_env_ignores_cache(probe_cache, monkeypatch):
    key = runtime._probe_cache_key(runtime._resolve_timeout(None))
    runtime._store_verdict(key, runtime._failure("backend init timed out after 75s", 75))
    monkeypatch.setenv("KART_JAX_REPROBE", "1")
    info = runtime.probe_backend()
    assert info["ok"] and not info.get("cached")  # real probe ran


def test_reprobe_repays_cached_failure(probe_cache, monkeypatch):
    """reprobe() on a failure adopted from the cache has no abandoned init
    thread to re-join — it must run a real probe with the extra budget."""
    key = runtime._probe_cache_key(runtime._resolve_timeout(None))
    runtime._store_verdict(key, runtime._failure("backend init timed out after 75s", 75))
    assert not runtime.probe_backend()["ok"]
    info = runtime.reprobe(60)
    assert info["ok"] and not info.get("cached")


def test_invalidate_probe_cache(probe_cache):
    runtime.probe_backend()
    assert probe_cache.exists()
    assert runtime.invalidate_probe_cache() == str(probe_cache)
    assert not probe_cache.exists()
    assert runtime.invalidate_probe_cache() is None  # idempotent


def test_cache_disabled_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("KART_PROBE_CACHE", "0")
    monkeypatch.setattr(runtime, "_probe_result", None)
    monkeypatch.setattr(runtime, "_probe_thread", None)
    monkeypatch.setattr(runtime, "_probe_box", None)
    assert runtime._probe_cache_path() is None
    info = runtime.probe_backend()
    assert not info.get("cached")


def test_cache_key_scopes(monkeypatch):
    k1 = runtime._probe_cache_key(75.0)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    k2 = runtime._probe_cache_key(75.0)
    monkeypatch.delenv("JAX_PLATFORMS")
    k3 = runtime._probe_cache_key(300.0)
    assert len({k1, k2, k3}) == 3


@pytest.mark.parametrize("pinned", [True, False])
def test_xla_cache_dir_is_placed_from_outside_or_fixed(
    pinned, monkeypatch, tmp_path
):
    """The compile-cache contract: where JAX_COMPILATION_CACHE_DIR is set,
    jax has adopted exactly that directory and the program sets no other
    (no machine-scoped child, nothing under ~); where it is not, the cache
    goes to one fixed path inside the checkout. The machine signature keeps
    keying the probe verdict only."""
    sig = runtime.machine_signature()
    assert sig == runtime.machine_signature() and len(sig) == 12
    assert f"machine={sig}" in runtime._probe_cache_key(75.0)

    captured = {}

    class FakeConfig:
        @staticmethod
        def update(k, v):
            captured[k] = v

    class FakeJax:
        config = FakeConfig()

    monkeypatch.delenv("KART_NO_XLA_CACHE", raising=False)
    if pinned:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "shared"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(runtime, "XLA_CACHE_DIR", str(tmp_path / ".jax_cache"))
    runtime._enable_persistent_cache(FakeJax())
    if pinned:
        assert "jax_compilation_cache_dir" not in captured
        assert not os.listdir(tmp_path)  # no directory of our own making
    else:
        assert captured["jax_compilation_cache_dir"] == str(tmp_path / ".jax_cache")
        assert os.path.isdir(captured["jax_compilation_cache_dir"])
    assert "jax_persistent_cache_min_compile_time_secs" in captured


def test_xla_cache_default_is_inside_the_checkout():
    """Unset, the cache path is fixed and inside the checkout: never the
    home directory, a temporary name, a pid or a time."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert runtime.XLA_CACHE_DIR == os.path.join(repo_root, ".jax_cache")


def test_probe_backend_async_then_join(probe_cache, monkeypatch):
    runtime.probe_backend_async()
    info = runtime.probe_backend()
    assert info["ok"]


def test_sharded_counts_skips_class_materialisation():
    """backend.counts() on the sharded backend is the count-only reduction,
    not classify-and-discard — parity with host counts still exact."""
    old, new = _pair(seed=11)
    want = classify_blocks_host(old, new)[2]
    assert BACKENDS["sharded_jax"].counts(old, new) == want


def test_stale_cached_ok_heals_on_failed_init(probe_cache, monkeypatch):
    """A persisted ok verdict is a promise, not proof: when the warm init
    behind it comes back failed, jax_ready() must answer False and rewrite
    the cache so later processes stop believing the stale ok."""
    import threading

    key = runtime._probe_cache_key(runtime._resolve_timeout(None))
    runtime._store_verdict(
        key,
        {
            "ok": True,
            "backend": "tpu",
            "device_kind": "fake",
            "n_devices": 8,
            "init_seconds": 1.0,
            "error": None,
        },
    )
    info = runtime.probe_backend()
    assert info["ok"] and info.get("cached") is True
    # simulate the warm-started init coming back broken (runtime died since
    # the verdict was written)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join()
    monkeypatch.setattr(runtime, "_probe_thread", t)
    monkeypatch.setattr(
        runtime, "_probe_box", {"result": runtime._failure("PJRT init exploded")}
    )
    assert runtime.jax_ready() is False
    saved = json.loads(probe_cache.read_text())
    assert saved[key]["ok"] is False  # the cache self-healed


def test_wedged_init_behind_cached_ok_is_bounded(probe_cache, monkeypatch):
    """The hang the watchdog exists to prevent must stay prevented when the
    verdict came from the cache: a wedged init behind a cached ok flips
    jax_ready() to False within the watchdog budget instead of letting the
    first jax call block forever."""
    import threading
    import time as _time

    monkeypatch.setenv("KART_JAX_INIT_TIMEOUT", "0.2")
    key = runtime._probe_cache_key(0.2)
    runtime._store_verdict(
        key,
        {
            "ok": True,
            "backend": "tpu",
            "device_kind": "fake",
            "n_devices": 8,
            "init_seconds": 1.0,
            "error": None,
        },
    )
    assert runtime.probe_backend()["ok"]
    wedge = threading.Event()
    t = threading.Thread(target=wedge.wait, daemon=True)
    t.start()
    monkeypatch.setattr(runtime, "_probe_thread", t)
    monkeypatch.setattr(runtime, "_probe_box", {})
    t0 = _time.perf_counter()
    assert runtime.jax_ready() is False
    assert _time.perf_counter() - t0 < 5  # bounded, not a hang
    assert json.loads(probe_cache.read_text())[key]["ok"] is False
    wedge.set()


def test_warm_probe_respects_disabled_device_paths(monkeypatch):
    """KART_DIFF_DEVICE=0 + KART_DIFF_SHARDED=0 means auto routing can only
    pick host_native — warm_probe must not background-start jax/PJRT init
    (the config a user sets precisely because the accelerator runtime is stuck)."""
    from kart_tpu.diff.backend import warm_probe

    monkeypatch.delenv("KART_DIFF_BACKEND", raising=False)
    monkeypatch.setenv("KART_DIFF_DEVICE", "0")
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    called = []
    monkeypatch.setattr(
        runtime, "probe_backend_async", lambda: called.append(1)
    )
    warm_probe(10**9)
    assert not called
    # one device path re-enabled: the warm kick is wanted again
    monkeypatch.setenv("KART_DIFF_SHARDED", "auto")
    warm_probe(10**9)
    assert called


def test_reprobe_repays_cached_failure_same_timeout(probe_cache, monkeypatch):
    """extra_timeout equal to the configured timeout makes the cache-lookup
    key match the dropped verdict: the re-pay must bypass the persisted
    failure rather than instantly re-adopt it."""
    timeout = runtime._resolve_timeout(None)
    key = runtime._probe_cache_key(timeout)
    runtime._store_verdict(
        key, runtime._failure(f"backend init timed out after {timeout:g}s", timeout)
    )
    assert not runtime.probe_backend()["ok"]
    info = runtime.reprobe(timeout)
    assert info["ok"] and not info.get("cached")  # a real probe ran


# -- every device→host rung counts itself (ISSUE 21) -------------------------

@pytest.fixture
def fallback_counter():
    """-> callable(what) reading diff.device.fallbacks{what=…}, with the
    metric registry armed for the test and cleared after it."""
    from kart_tpu import telemetry as tm

    tm.reset()
    tm.enable(metrics=True)
    yield lambda what: tm.counters_snapshot().get(
        ("diff.device.fallbacks", (("what", what),)), 0
    )
    tm.reset()


def _boom(*args, **kwargs):
    raise RuntimeError("device call made to raise")


def test_classify_rung_counts_its_fallback(monkeypatch, fallback_counter):
    """classify_blocks' device→host rung: the right answer still comes
    back, and the rung is visible as diff.device.fallbacks{what=
    device_classify} — a chip that never answered can no longer exit 0
    unnoticed."""
    from kart_tpu.ops import diff_kernel

    old, new = _pair(seed=5)
    want = classify_blocks_host(old, new)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(diff_kernel, "_classify_split", _boom)
    monkeypatch.setattr(diff_kernel, "_classify_window_split", _boom)
    got = diff_kernel.classify_blocks(old, new)
    assert got[2] == want[2]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert fallback_counter("device_classify") == 1


def _merge_triple(seed=3, n=3000):
    anc, _ = _pair(n=n, seed=seed)
    ours = FeatureBlock(anc.keys, anc.oids.copy(), None, anc.count)
    theirs = FeatureBlock(anc.keys, anc.oids.copy(), None, anc.count)
    ours.oids[: anc.count : 10, 0] ^= 1
    theirs.oids[: anc.count : 10, 0] ^= 2  # conflicts with ours
    theirs.oids[7 : anc.count : 20, 1] ^= 3  # theirs-only edits
    return anc, ours, theirs


@pytest.mark.parametrize(
    "what, sharded, broken",
    [
        ("device_classify", "0", "kart_tpu.ops.diff_kernel.classify_blocks_streamed"),
        ("classify", "1", "kart_tpu.diff.device_batch.classify_blocks_batched"),
    ],
)
def test_merge_rungs_count_their_fallback(
    what, sharded, broken, monkeypatch, fallback_counter
):
    """A merge whose device classify raises — one chip's
    (``device_classify``), the mesh's (``classify``) — falls back as its two
    diffs do: the same decisions as the host engine, the diff's rung
    counted once a diff, and the span names the backend the ladder picked
    (the convention of ``diff.classify``)."""
    from kart_tpu import telemetry as tm
    from kart_tpu.diff.backend import merge_classify

    blocks = _merge_triple()
    monkeypatch.setenv("KART_DIFF_DEVICE", "0")
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    want = merge_classify(*blocks)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1" if sharded == "0" else "0")
    monkeypatch.setenv("KART_DIFF_SHARDED", sharded)
    monkeypatch.setattr(broken, _boom)
    tm.enable(trace=True)
    tm.drain_events()
    got = merge_classify(*blocks)
    spans = [e for e in tm.drain_events() if e["name"] == "diff.merge_classify"]
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(g, w)
    assert got[3] == want[3] and got[3]["conflicts"] == 300
    assert fallback_counter(what) == 2
    assert [s["args"]["backend"] for s in spans] == [
        "sharded_jax" if sharded == "1" else "device_jax"
    ]


def test_merge_span_names_the_backend_that_answered(monkeypatch):
    """diff.merge_classify carries backend=, the merge twin of
    diff.classify's attribute: host on XLA-CPU auto routing, the device
    kernel when forced, the mesh when forced."""
    from kart_tpu import telemetry as tm
    from kart_tpu.diff.backend import merge_classify

    blocks = _merge_triple(seed=4)
    tm.reset()
    tm.enable(trace=True)
    try:
        seen = []
        for device, sharded in (("auto", "auto"), ("1", "0"), ("0", "1")):
            monkeypatch.setenv("KART_DIFF_DEVICE", device)
            monkeypatch.setenv("KART_DIFF_SHARDED", sharded)
            merge_classify(*blocks)
            seen += [
                e["args"]["backend"]
                for e in tm.drain_events()
                if e["name"] == "diff.merge_classify"
            ]
    finally:
        tm.reset()
    assert seen == ["host_native", "device_jax", "sharded_jax"]


# -- chip_smoke.py with no chip ----------------------------------------------

def test_chip_smoke_walks_every_phase_and_fails_without_a_chip(tmp_path):
    """`python chip_smoke.py` on XLA-CPU at tiny sizes: every phase is
    walked (none raises), auto routing keeps XLA-CPU on host_native, and the
    run ends non-zero with "ok": false and a CPU platform in its last line —
    for the right reasons, not for a crash."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a one-chip machine
    for k in [k for k in env if k.startswith("KART_DIFF_")]:
        del env[k]
    proc = subprocess.run(
        [
            sys.executable, os.path.join(repo_root, "chip_smoke.py"),
            "--rows", "20000", "--cli-merge-rows", "3000",
            "--cli-merge-conflicts", "40", "--merge-rows", "20000",
            "--merge-conflicts", "4000", "--envelopes", "30000",
            "--jsonl-rows", "3000",
        ],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    records = [json.loads(line) for line in proc.stdout.splitlines()]
    last = records[-1]
    assert last == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    phases = {r["phase"]: r for r in records[:-1]}
    assert not [p for p, r in phases.items() if "error" in r], proc.stderr[-3000:]
    assert {
        "native", "device", "diff.feature_count", "diff.json_lines",
        "merge.cli", "merge.blocks", "bbox", "materialise",
        "mesh1.classify_batched", "mesh1.sampled_counts_pmapped",
        "mesh1.envelope_hits", "mesh1.merc_envelopes", "mesh1.join_counts",
        "mesh1.refine_pairs", "summary",
    } <= set(phases)
    assert phases["device"]["checks"]["platform_is_tpu"] is False
    for name in ("diff.feature_count", "diff.json_lines", "merge.cli"):
        rec = phases[name]
        assert rec["backend"] == ["host_native"] and not rec["ok"]
        assert all(v for k, v in rec["checks"].items() if k != "backend"), rec
    assert phases["summary"]["fallbacks_total"] == 0
