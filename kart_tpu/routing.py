"""Which engine answers — the host, one device, or the mesh: the one place
that decides (docs/DEVICE.md §1, §8), asked by ``diff/backend.py``,
``ops/diff_kernel.py`` and ``ops/bbox.py``.

The ladder, cheapest test first: forcing knob → row floor (before any jax
import, so a small ``kart diff`` stays instant with the accelerator cold or
its runtime wedged) → ``jax_ready()`` (the watchdogged probe) → XLA-CPU
refusal → two devices or more → the mesh, else one device. The narrower
questions are the same ladder read at one rung: :func:`runtime_ready` stops
after ``jax_ready()``, :func:`device_open` after the XLA-CPU refusal,
:func:`mesh_open` after the device count.

The forcing knobs are read here and nowhere else in the program:

* ``KART_DIFF_BACKEND=<engine>`` — :func:`select_engine` answers that
  engine for every row count (an unknown name warns and routes auto);
  ``host_native`` also closes every device route below it (bbox).
* ``KART_DIFF_DEVICE=1|0`` — the one-device route: ``1`` forces it past the
  floor and the XLA-CPU refusal (tests, experiments), ``0`` closes it.
* ``KART_DIFF_SHARDED=1|0`` — the same for the mesh route.

Imports ``kart_tpu.runtime`` and nothing above it.
"""

import logging
import os

L = logging.getLogger("kart_tpu.routing")

ENGINES = ("host_native", "device_jax", "sharded_jax")

# below this row count the host engine beats the device round trip. The value
# dates from round 2 (numpy 0.35 s vs device 1.85 s at 1M rows,
# transfer-dominated) and predates the native host engine and the windowed
# join; re-tuning it needs chip numbers for both engines in one cell
# (ROADMAP queue 1 #3).
DEVICE_MIN_ROWS = 2_000_000

# below this the mesh round trip loses to the single-device kernel (per-shard
# padding); tied to the device crossover so the two move together
SHARDED_MIN_ROWS = DEVICE_MIN_ROWS

# below this count the host envelope scan wins outright; measured crossover
# on TPU v5e: numpy wins to ~1M envelopes, the device kernel is ~7x faster at
# 10M. The device-resident column cache routes at the same crossover (same
# float32 rounding trade, so a cache_key never changes results)
DEVICE_MIN_ENVELOPES = 1_000_000


def _backend_knob():
    return os.environ.get("KART_DIFF_BACKEND", "auto")


def runtime_ready(n_rows, floor, forced=False):
    """The ladder up to ``jax_ready()``: no route is open below ``floor``
    (unless ``forced``) — decided before any jax import — nor with the
    runtime unusable, nor under ``KART_DIFF_BACKEND=host_native``."""
    if _backend_knob() == "host_native":
        return False
    if n_rows < floor and not forced:
        return False
    from kart_tpu.runtime import jax_ready

    return jax_ready()


def _route_open(knob, n_rows, floor):
    """The ladder up to the XLA-CPU refusal, under one route's knob. On an
    XLA-**CPU** backend the host engine wins at every size: the native C++
    merge-join is sequential-scan bound (~1.1 s at 100M rows) where the XLA
    join lost 13.6x (measured r3: 65.3 s vs 4.8 s). XLA-CPU and its virtual
    mesh exist for correctness twins and tests, not as a production engine."""
    mode = os.environ.get(knob, "auto")
    if mode == "0" or not runtime_ready(n_rows, floor, forced=mode == "1"):
        return False
    from kart_tpu.runtime import default_backend

    return mode == "1" or default_backend() != "cpu"


def device_open(n_rows, floor=DEVICE_MIN_ROWS):
    """May ``n_rows`` go to one device? (``KART_DIFF_DEVICE`` forces.)"""
    return _route_open("KART_DIFF_DEVICE", n_rows, floor)


def mesh_open(n_rows, floor=SHARDED_MIN_ROWS):
    """May ``n_rows`` go to the mesh? (``KART_DIFF_SHARDED`` forces, but
    never past the device count: a mesh needs two.)"""
    if not _route_open("KART_DIFF_SHARDED", n_rows, floor):
        return False
    import jax

    return jax.device_count() >= 2


def select_engine(n_rows):
    """The engine the production diff path runs ``n_rows`` through: one of
    :data:`ENGINES`. A malformed ``KART_DIFF_BACKEND`` must never kill the
    CLI: unknown names warn and route auto."""
    mode = _backend_knob()
    if mode in ENGINES:
        return mode
    if mode != "auto":
        L.warning(
            "unknown KART_DIFF_BACKEND=%r (have: %s); using auto routing",
            mode,
            ", ".join(sorted(ENGINES)),
        )
    if mesh_open(n_rows):
        return "sharded_jax"
    if device_open(n_rows):
        return "device_jax"
    return "host_native"


def mesh_or_host(n_rows):
    """The engine for a workload that has a mesh program and a host twin
    and nothing for one device (tile projection, spatial join, exact
    refine): ``sharded_jax`` or ``host_native``. ``KART_DIFF_DEVICE=0`` and
    a ``KART_DIFF_BACKEND`` naming another engine close the mesh here too."""
    if (
        os.environ.get("KART_DIFF_DEVICE") != "0"
        and _backend_knob() in ("auto", "sharded_jax")
        and mesh_open(n_rows)
    ):
        return "sharded_jax"
    return "host_native"


def any_device_route(n_rows):
    """Could any device route take ``n_rows``? Knobs and floors alone — it
    never touches jax: the question ``warm_probe`` asks before it starts
    the runtime in the background, so a configuration that closed every
    device route (a known stuck accelerator) never starts it at all."""
    mode = _backend_knob()
    if mode == "host_native":
        return False
    if (
        mode == "auto"
        and os.environ.get("KART_DIFF_DEVICE") == "0"
        and os.environ.get("KART_DIFF_SHARDED") == "0"
    ):
        return False  # auto routing can only ever pick host_native
    return n_rows >= min(DEVICE_MIN_ROWS, SHARDED_MIN_ROWS)
