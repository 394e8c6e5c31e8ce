"""Mesh-sharded diff classification (SURVEY.md §7 step 7).

Blocks are partitioned host-side by ``key % n_shards`` — block-cyclic over
PK-space, the device analog of kart's PathEncoder modulus sharding
(`kart/dataset3_paths.py:283-299`). Because the partition function depends
only on the key, a feature lands on the same shard in every revision, so the
old↔new merge-join of the diff engine (`kart_tpu/ops/diff_kernel.py`) is
fully shard-local: zero feature data crosses the interconnect. Only the
3-scalar insert/update/delete count vector is reduced with ``psum`` over ICI.

The sharded step is expressed with ``shard_map`` over a 1-D ``Mesh`` so the
same program runs on a real slice or on a virtual CPU mesh (the driver's
``dryrun_multichip``), and on one device it degenerates to the single-chip
kernel.
"""

import functools

import numpy as np

from kart_tpu.ops import blocks as blocks_mod
from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock, bucket_size
from kart_tpu.ops.diff_kernel import DELETE, INSERT, UNCHANGED, UPDATE
from kart_tpu.parallel.mesh import FEATURES_AXIS

# jax is imported inside functions only: `kart diff` on a small repo routes
# through this module's should_shard() and must stay instant (no jax import,
# no backend probe) when the mesh path can't win anyway.


def partition_block(block, n_shards, min_bucket=256):
    """FeatureBlock -> (keys (S, B) int64, oids (S, B, 5) uint32,
    counts (S,) int32, src (S, B) int64): PK-modulus partition, each shard
    sorted + padded to a common power-of-two bucket B. ``src`` maps each
    shard slot back to the original block row (-1 for padding), so per-shard
    results scatter back to block order.

    Shard order inside a bucket remains key-sorted, so per-shard joins have
    identical semantics to the single-chip path.
    """
    real_keys = block.keys[: block.count]
    real_oids = block.oids[: block.count]
    shard_of = (real_keys % n_shards).astype(np.int64)
    counts = np.bincount(shard_of, minlength=n_shards).astype(np.int32)
    bucket = bucket_size(max(int(counts.max()) if len(counts) else 1, 1), min_bucket)

    keys = np.full((n_shards, bucket), PAD_KEY, dtype=np.int64)
    oids = np.zeros((n_shards, bucket, 5), dtype=np.uint32)
    src = np.full((n_shards, bucket), -1, dtype=np.int64)
    # real_keys is globally sorted; a stable partition keeps each shard sorted
    order = np.argsort(shard_of, kind="stable")
    offsets = np.zeros(n_shards + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    sorted_keys = real_keys[order]
    sorted_oids = real_oids[order]
    for s in range(n_shards):
        lo, hi = offsets[s], offsets[s + 1]
        keys[s, : hi - lo] = sorted_keys[lo:hi]
        oids[s, : hi - lo] = sorted_oids[lo:hi]
        src[s, : hi - lo] = order[lo:hi]
    return keys, oids, counts, src


def _local_classify(old_keys, old_oids, new_keys, new_oids, old_count, new_count):
    """Per-shard classify: the same sort-based merge-join as the single-chip
    flagship kernel, applied to the (B,) shard-local slice (shapes inside
    shard_map)."""
    from kart_tpu.ops.diff_kernel import _classify_mergesort_core

    old_class, new_class, _, counts = _classify_mergesort_core(
        old_keys, old_oids, new_keys, new_oids, old_count, new_count
    )
    return old_class, new_class, counts


def _sharded_step(old_keys, old_oids, new_keys, new_oids, old_counts, new_counts):
    """shard_map body: input shapes are the (1, B[, 5]) per-device slices of
    the stacked (S, B[, 5]) arrays. Counts cross the mesh via psum."""
    import jax

    old_class, new_class, counts = _local_classify(
        old_keys[0],
        old_oids[0],
        new_keys[0],
        new_oids[0],
        old_counts[0],
        new_counts[0],
    )
    total = jax.lax.psum(counts, FEATURES_AXIS)
    return old_class[None], new_class[None], total


@functools.lru_cache(maxsize=8)
def make_sharded_classify(mesh):
    """Build the jitted mesh-sharded classify for ``mesh``. Arguments are the
    stacked outputs of :func:`partition_block` (leading dim == mesh size).
    Cached per mesh so repeat calls reuse the compiled executable (Mesh is
    hashable)."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(FEATURES_AXIS)
    repl = P()
    fn = jax.shard_map(
        _sharded_step,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, spec),
        out_specs=(spec, spec, repl),
    )
    return jax.jit(fn)


def sharded_classify(mesh, old_block, new_block):
    """FeatureBlock x2 -> per-shard classes + global counts over ``mesh``.

    Returns (old_class (S, B) int8, new_class (S, B) int8,
    counts {inserts, updates, deletes},
    layout = (old_part, new_part) for mapping shard rows back to features).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_shards = mesh.devices.size
    old_part = partition_block(old_block, n_shards)
    new_part = partition_block(new_block, n_shards)
    # shards of a pair must share a bucket size: re-pad the smaller
    bucket = max(old_part[0].shape[1], new_part[0].shape[1])
    old_part = _repad(old_part, bucket)
    new_part = _repad(new_part, bucket)

    fn = make_sharded_classify(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    args = []
    for arr in (old_part[0], old_part[1], new_part[0], new_part[1]):
        args.append(jax.device_put(arr, sharding))
    for arr in (old_part[2], new_part[2]):
        args.append(jax.device_put(arr, sharding))
    # arg order: (old_keys, old_oids, new_keys, new_oids, old_counts, new_counts)
    old_class, new_class, counts = fn(*args)
    counts = np.asarray(counts)
    return (
        np.asarray(old_class),
        np.asarray(new_class),
        {
            "inserts": int(counts[0]),
            "updates": int(counts[1]),
            "deletes": int(counts[2]),
        },
        (old_part, new_part),
    )


def _repad(part, bucket):
    keys, oids, counts, src = part
    cur = keys.shape[1]
    if cur >= bucket:
        return part
    s = keys.shape[0]
    keys2 = np.full((s, bucket), PAD_KEY, dtype=np.int64)
    keys2[:, :cur] = keys
    oids2 = np.zeros((s, bucket, 5), dtype=np.uint32)
    oids2[:, :cur] = oids
    src2 = np.full((s, bucket), -1, dtype=np.int64)
    src2[:, :cur] = src
    return keys2, oids2, counts, src2


def sharded_diff_step(mesh, old_block, new_block):
    """The "full step" the driver dry-runs: partition, classify on the mesh,
    reduce counts. Returns the counts dict."""
    _, _, counts, _ = sharded_classify(mesh, old_block, new_block)
    return counts


# observability: how many times the mesh path actually ran this process
# (dryrun_multichip and tests assert on it — the single-chip path silently
# taking over would otherwise be invisible)
STATS = {"sharded_classify_calls": 0, "sharded_merge_calls": 0}

# below this row count the mesh round trip loses to the single-device kernel
# (partition + per-shard padding overhead); tied to the device dispatch
# crossover so the two routing constants move together, own env knob on top.
# Force with KART_DIFF_SHARDED=1/0.
def _sharded_min_rows():
    from kart_tpu.ops.diff_kernel import DEVICE_MIN_ROWS, _env_int

    return _env_int("KART_SHARDED_MIN_ROWS", DEVICE_MIN_ROWS)


def should_shard(n_rows):
    """Routing policy for the production diff path: use the mesh when it
    exists and the block is big enough to pay for partitioning.

    Ordered cheapest-first: the row-count test runs before any jax import or
    backend probe, so a small `kart diff` stays instant even with the
    accelerator wedged or cold (same guarantee as classify_blocks)."""
    import os

    mode = os.environ.get("KART_DIFF_SHARDED", "auto")
    if mode == "0":
        return False
    if mode != "1" and n_rows < _sharded_min_rows():
        return False
    from kart_tpu.runtime import default_backend, jax_ready

    if not jax_ready():
        return False
    if mode != "1" and default_backend() == "cpu":
        # a virtual CPU mesh is a test/dryrun vehicle, not a production
        # engine: the native host merge-join wins XLA-CPU at every size
        # (same cost model as ops.diff_kernel.device_profitable)
        return False
    import jax

    return jax.device_count() >= 2


def _scatter_to_block_order(part_class, src, n_rows):
    """(S, B) per-shard classes + (S, B) src rows -> (n_rows,) block-order
    classes (UNCHANGED where padded)."""
    out = np.zeros(n_rows, dtype=np.int8)
    valid = src >= 0
    out[src[valid]] = np.asarray(part_class)[valid]
    return out


def classify_blocks_sharded(old_block, new_block, mesh=None):
    """Mesh-sharded drop-in for ``ops.diff_kernel.classify_blocks``: same
    contract — (old_class (n_old,), new_class (n_new,), counts dict) in
    original block-row order — but the classify runs shard-local on every
    device of ``mesh`` (default: all devices) with only the count vector
    crossing the interconnect. This is the production multi-chip diff path
    (the reference's N-process import fan-out, `kart/fast_import.py:286-399`,
    re-expressed as SPMD over the feature axis)."""
    from kart_tpu.parallel.mesh import make_mesh

    try:
        if mesh is None:
            mesh = make_mesh()
        old_class_p, new_class_p, counts, (old_part, new_part) = sharded_classify(
            mesh, old_block, new_block
        )
    except Exception as e:
        # device OOM / runtime failure mid-call: fall back to the single-chip
        # route, which itself degrades to the host engine — the CLI must
        # still complete (same guarantee classify_blocks gives)
        from kart_tpu.ops.diff_kernel import (
            classify_blocks,
            note_device_fallback,
        )

        note_device_fallback("block_cyclic_classify", e, "single-chip path")
        return classify_blocks(old_block, new_block)
    STATS["sharded_classify_calls"] += 1
    old_class = _scatter_to_block_order(old_class_p, old_part[3], old_block.count)
    new_class = _scatter_to_block_order(new_class_p, new_part[3], new_block.count)
    return old_class, new_class, counts


def synthetic_block(n, seed=0, change_none=False):
    """Synthetic FeatureBlock for benchmarks/dryruns: keys 0..n-1 with random
    oids (deterministic per seed)."""
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    paths = None  # benchmarks never materialise values
    block = FeatureBlock.__new__(FeatureBlock)
    size = bucket_size(max(n, 1))
    if size > n:
        keys = np.concatenate([keys, np.full(size - n, PAD_KEY, dtype=np.int64)])
        oids = np.concatenate([oids, np.zeros((size - n, 5), dtype=np.uint32)])
    block.keys = keys
    block.oids = oids
    block.paths = paths
    block.count = n
    return block
