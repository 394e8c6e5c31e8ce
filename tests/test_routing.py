"""kart_tpu.routing: the one ladder, as a table (docs/DEVICE.md §1, §8).

Each case is (forcing knobs, platform, device count, rows) -> what every
question of the module answers. The platform is simulated — a probe verdict
naming the backend, ``jax.device_count`` patched — so the TPU rows run on
the suite's CPU.
"""

import numpy as np
import pytest

import jax

from kart_tpu import routing, runtime

SMALL = 1_000  # below every floor
BIG = 10_000_000  # above every floor (the benchmark cells' rows)

HOST_TWIN = {
    "KART_DIFF_BACKEND": "host_native",
    "KART_DIFF_DEVICE": "0",
    "KART_DIFF_SHARDED": "0",
}

# knobs, platform, devices, rows -> engine, device_open, mesh_open, mesh_or_host
CASES = [
    # auto routing: docs/DEVICE.md §8's table
    ({}, "tpu", 1, SMALL, "host_native", False, False, "host_native"),
    ({}, "tpu", 4, SMALL, "host_native", False, False, "host_native"),
    ({}, "tpu", 1, BIG, "device_jax", True, False, "host_native"),
    ({}, "tpu", 4, BIG, "sharded_jax", True, True, "sharded_jax"),
    ({}, "cpu", 1, BIG, "host_native", False, False, "host_native"),
    ({}, "cpu", 4, BIG, "host_native", False, False, "host_native"),
    ({}, None, 0, BIG, "host_native", False, False, "host_native"),  # wedged
    # KART_DIFF_SHARDED: the mesh route's knob
    ({"KART_DIFF_SHARDED": "1"}, "cpu", 4, SMALL, "sharded_jax", False, True, "sharded_jax"),
    ({"KART_DIFF_SHARDED": "1"}, "cpu", 1, SMALL, "host_native", False, False, "host_native"),
    ({"KART_DIFF_SHARDED": "1"}, "tpu", 1, BIG, "device_jax", True, False, "host_native"),
    ({"KART_DIFF_SHARDED": "0"}, "tpu", 4, BIG, "device_jax", True, False, "host_native"),
    # KART_DIFF_DEVICE: the one-device route's knob
    ({"KART_DIFF_DEVICE": "1"}, "cpu", 1, SMALL, "device_jax", True, False, "host_native"),
    ({"KART_DIFF_DEVICE": "1"}, "cpu", 4, SMALL, "device_jax", True, False, "host_native"),
    ({"KART_DIFF_DEVICE": "0"}, "tpu", 1, BIG, "host_native", False, False, "host_native"),
    ({"KART_DIFF_DEVICE": "0"}, "tpu", 4, BIG, "sharded_jax", False, True, "host_native"),
    ({"KART_DIFF_DEVICE": "0", "KART_DIFF_SHARDED": "1"}, "cpu", 4, SMALL,
     "sharded_jax", False, True, "host_native"),
    ({"KART_DIFF_DEVICE": "1", "KART_DIFF_SHARDED": "0"}, "cpu", 4, SMALL,
     "device_jax", True, False, "host_native"),
    ({"KART_DIFF_DEVICE": "0", "KART_DIFF_SHARDED": "0"}, "tpu", 4, BIG,
     "host_native", False, False, "host_native"),
    # KART_DIFF_BACKEND: names the engine; the narrower questions keep
    # their floors
    ({"KART_DIFF_BACKEND": "device_jax"}, "cpu", 1, SMALL, "device_jax", False, False, "host_native"),
    ({"KART_DIFF_BACKEND": "device_jax"}, "tpu", 4, BIG, "device_jax", True, True, "host_native"),
    ({"KART_DIFF_BACKEND": "sharded_jax"}, "tpu", 4, SMALL, "sharded_jax", False, False, "host_native"),
    ({"KART_DIFF_BACKEND": "sharded_jax"}, "tpu", 4, BIG, "sharded_jax", True, True, "sharded_jax"),
    ({"KART_DIFF_BACKEND": "warp_drive"}, "tpu", 4, BIG, "sharded_jax", True, True, "host_native"),
    # host_native closes every device route (until PR 30 device_open and
    # mesh_open did not read it: merge ignored the knob)
    ({"KART_DIFF_BACKEND": "host_native"}, "tpu", 4, BIG, "host_native", False, False, "host_native"),
    (HOST_TWIN, "tpu", 4, BIG, "host_native", False, False, "host_native"),
]


def _case_id(case):
    knobs, platform, devices, rows = case[:4]
    knob_names = {"KART_DIFF_BACKEND": "B", "KART_DIFF_DEVICE": "D", "KART_DIFF_SHARDED": "S"}
    said = ",".join(f"{knob_names[k]}={v}" for k, v in knobs.items()) or "auto"
    return f"{said}-{platform}x{devices}-{'big' if rows == BIG else 'small'}"


@pytest.fixture
def platform(monkeypatch):
    """-> set(platform, devices): the runtime this process believes in."""
    for knob in HOST_TWIN:
        monkeypatch.delenv(knob, raising=False)

    def set_platform(name, devices):
        monkeypatch.setattr(
            runtime,
            "_probe_result",
            {"ok": name is not None, "backend": name, "device_kind": name,
             "n_devices": devices, "init_seconds": 0.0,
             "error": None if name else "simulated"},
        )
        monkeypatch.setattr(jax, "device_count", lambda: devices)

    return set_platform


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_ladder(case, platform, monkeypatch):
    knobs, name, devices, rows, engine, device_open, mesh_open, mesh_or_host = case
    platform(name, devices)
    for knob, value in knobs.items():
        monkeypatch.setenv(knob, value)
    assert routing.select_engine(rows) == engine
    assert routing.device_open(rows) is device_open
    assert routing.mesh_open(rows) is mesh_open
    assert routing.mesh_or_host(rows) == mesh_or_host


@pytest.mark.parametrize("forced", [False, True], ids=["auto", "sharded_jax_named"])
def test_below_the_floor_nothing_touches_the_runtime(forced, platform, monkeypatch):
    """The row floor is tested before any jax import or backend probe: a
    small `kart diff` stays instant with the accelerator cold or wedged."""
    platform("tpu", 4)
    monkeypatch.setattr(runtime, "_probe_result", None)

    def touched(*a, **k):
        raise AssertionError("the runtime was asked below the floor")

    monkeypatch.setattr(runtime, "jax_ready", touched)
    monkeypatch.setattr(runtime, "probe_backend_async", touched)
    monkeypatch.setattr(jax, "device_count", touched)
    if forced:
        monkeypatch.setenv("KART_DIFF_BACKEND", "sharded_jax")
    assert not routing.device_open(SMALL)
    assert not routing.mesh_open(SMALL)
    assert not routing.runtime_ready(SMALL, routing.DEVICE_MIN_ENVELOPES)
    assert not routing.any_device_route(SMALL)
    assert routing.mesh_or_host(SMALL) == "host_native"
    if not forced:
        assert routing.select_engine(SMALL) == "host_native"
    assert runtime._probe_result is None  # no probe happened


@pytest.mark.parametrize(
    "knobs, rows, wanted",
    [
        ({}, BIG, True),
        ({}, SMALL, False),
        ({"KART_DIFF_BACKEND": "host_native"}, BIG, False),
        ({"KART_DIFF_DEVICE": "0", "KART_DIFF_SHARDED": "0"}, BIG, False),
        ({"KART_DIFF_DEVICE": "0"}, BIG, True),  # the mesh route is still open
        ({"KART_DIFF_BACKEND": "sharded_jax", "KART_DIFF_DEVICE": "0",
          "KART_DIFF_SHARDED": "0"}, BIG, True),
    ],
)
def test_any_device_route_reads_knobs_and_floor_alone(
    knobs, rows, wanted, platform, monkeypatch
):
    platform(None, 0)  # a wedged runtime: the answer must not depend on it
    for knob, value in knobs.items():
        monkeypatch.setenv(knob, value)
    assert routing.any_device_route(rows) is wanted


def test_host_native_closes_merge_and_bbox_too(platform, monkeypatch):
    """KART_DIFF_BACKEND=host_native alone sends the merge and the envelope
    scan to the host engine on a four-chip TPU host at any size."""
    from kart_tpu import telemetry as tm
    from kart_tpu.diff import backend as B
    from kart_tpu.ops import bbox
    from kart_tpu.parallel.sharded_diff import synthetic_block

    platform("tpu", 4)
    monkeypatch.setenv("KART_DIFF_BACKEND", "host_native")
    monkeypatch.setattr(routing, "DEVICE_MIN_ROWS", 0)
    monkeypatch.setattr(routing, "SHARDED_MIN_ROWS", 0)
    monkeypatch.setattr(routing, "DEVICE_MIN_ENVELOPES", 0)

    def device_call(*a, **k):
        raise AssertionError("a device program was called")

    monkeypatch.setattr(bbox, "bbox_intersects_pallas", device_call)
    monkeypatch.setattr(bbox, "bbox_intersects_jnp", device_call)
    env = np.array([[0.0, 0.0, 1.0, 1.0], [5.0, 5.0, 6.0, 6.0]])
    assert bbox.bbox_intersects(env, (0.5, 0.5, 2.0, 2.0)).tolist() == [True, False]

    blocks = [synthetic_block(300, seed=s) for s in (0, 0, 1)]
    tm.reset()
    tm.enable(trace=True)
    try:
        B.merge_classify(*blocks)
        spans = [e for e in tm.drain_events() if e["name"] == "diff.merge_classify"]
    finally:
        tm.reset()
    assert [s["args"]["backend"] for s in spans] == ["host_native"]
