"""The mesh paths reached from the CLI and the routers, the merge's two
diffs among them: identical to the single-chip and host paths. (The mesh
diff's own tests are tests/test_device_batch.py.)

Runs on whatever devices are live; the multi-device cases skip below 2
devices (use the virtual CPU mesh per tests/conftest.py).
"""

import numpy as np
import pytest

import jax

from kart_tpu.ops.blocks import FeatureBlock, pack_oid_hex
from kart_tpu.parallel.sharded_diff import synthetic_block


def _blocks_with_edits(n=1000, n_ins=7, n_upd=11, n_del=5, seed=42):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(10 * n, size=n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    paths = [f"f/{k}" for k in keys]
    old = FeatureBlock.from_arrays(keys.copy(), oids.copy(), list(paths))

    new_keys = keys.copy()
    new_oids = oids.copy()
    del_idx = rng.choice(n, size=n_del, replace=False)
    keep = np.setdiff1d(np.arange(n), del_idx)
    new_keys = new_keys[keep]
    new_oids = new_oids[keep]
    upd_idx = rng.choice(len(new_keys), size=n_upd, replace=False)
    new_oids[upd_idx] = rng.integers(0, 2**32, size=(n_upd, 5), dtype=np.uint32)
    ins_keys = np.asarray(
        sorted(set(range(10 * n, 10 * n + n_ins))), dtype=np.int64
    )
    ins_oids = rng.integers(0, 2**32, size=(n_ins, 5), dtype=np.uint32)
    new_keys = np.concatenate([new_keys, ins_keys])
    new_oids = np.concatenate([new_oids, ins_oids])
    new_paths = [f"f/{k}" for k in new_keys]
    new = FeatureBlock.from_arrays(new_keys, new_oids, new_paths)
    return old, new, {"inserts": n_ins, "updates": n_upd, "deletes": n_del}


def test_mesh_open_env_override(monkeypatch):
    """On the suite's real (virtual CPU) devices, not a simulated platform
    as tests/test_routing.py."""
    from kart_tpu.routing import mesh_open

    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    assert not mesh_open(10**9)
    monkeypatch.setenv("KART_DIFF_SHARDED", "1")
    if jax.device_count() >= 2:
        assert mesh_open(10)
    monkeypatch.setenv("KART_DIFF_SHARDED", "auto")
    assert not mesh_open(10)  # far below the crossover


def test_engine_routes_through_mesh(tmp_path, monkeypatch):
    """A real CLI diff (repo + sidecars) runs the mesh path when forced —
    the VERDICT r2 gap: sharding must be reachable from `kart diff`, not
    only from synthetic blocks."""
    import json

    from helpers import make_repo_with_edits

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from kart_tpu.parallel.sharded_diff import STATS

    repo_path, expected = make_repo_with_edits(tmp_path)
    monkeypatch.setenv("KART_DIFF_SHARDED", "1")
    monkeypatch.setenv("KART_DIFF_ENGINE", "columnar")
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    before = STATS["sharded_classify_calls"]
    result = CliRunner().invoke(
        cli,
        ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert STATS["sharded_classify_calls"] > before
    diff = json.loads(result.output)["kart.diff/v1+hexwkb"]
    ds = diff[next(iter(diff))]
    assert len(ds["feature"]) == sum(expected.values())


def test_synthetic_block_deterministic():
    a = synthetic_block(100, seed=1)
    b = synthetic_block(100, seed=1)
    assert np.array_equal(a.oids, b.oids)
    assert a.count == 100


def _merge_blocks(n=3000, seed=9):
    """(ancestor, ours, theirs) with a known mix of edits/conflicts."""
    from kart_tpu.parallel.sharded_diff import synthetic_block

    anc = synthetic_block(n, seed=seed)
    ours = synthetic_block(n, seed=seed)
    ours.oids = ours.oids.copy()
    theirs = synthetic_block(n, seed=seed)
    theirs.oids = theirs.oids.copy()
    rng = np.random.default_rng(seed + 1)
    both = rng.choice(n, size=n // 10, replace=False)  # conflicts
    ours_only = rng.choice(n, size=n // 7, replace=False)
    theirs_only = rng.choice(n, size=n // 5, replace=False)
    ours.oids[both, 0] ^= 1
    theirs.oids[both, 0] ^= 2
    ours.oids[ours_only, 1] ^= 3
    theirs.oids[theirs_only, 2] ^= 4
    return anc, ours, theirs


@pytest.mark.parametrize(
    "knobs",
    [{"KART_DIFF_BACKEND": "sharded_jax"}, {"KART_DIFF_SHARDED": "1"}],
    ids=["backend_named", "sharded_knob"],
)
def test_merge_classify_routes_through_mesh(knobs, monkeypatch):
    """The mesh backend named, or KART_DIFF_SHARDED=1: merge_classify's two
    diffs are the mesh's record-batch classify (two calls), and the union,
    decisions, presence bits and stats are the host engine's."""
    from kart_tpu.diff.backend import merge_classify
    from kart_tpu.parallel.sharded_diff import STATS

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    blocks = _merge_blocks(n=1500, seed=4)
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    expected = merge_classify(*blocks)
    for knob, value in knobs.items():
        monkeypatch.setenv(knob, value)
    before = STATS["sharded_classify_calls"]
    got = merge_classify(*blocks)
    assert STATS["sharded_classify_calls"] == before + 2
    for a, b in zip(got[:3], expected[:3]):
        np.testing.assert_array_equal(a, b)
    assert got[3] == expected[3] and got[3]["conflicts"] > 0


def test_estimation_routes_through_mesh(monkeypatch):
    """Device-sharded estimation rides the mesh when forced, matching the
    single-chip estimate."""
    from kart_tpu.diff.estimation import estimate_counts_from_blocks
    from kart_tpu.parallel.sharded_diff import STATS, synthetic_block

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    old, new, expected = _blocks_with_edits(n=4096, n_ins=11, n_upd=37, n_del=13)
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    single = estimate_counts_from_blocks(old, new, "good")
    monkeypatch.setenv("KART_DIFF_SHARDED", "1")
    before = STATS["sharded_classify_calls"]
    sharded = estimate_counts_from_blocks(old, new, "good")
    assert STATS["sharded_classify_calls"] > before
    assert sharded == single
