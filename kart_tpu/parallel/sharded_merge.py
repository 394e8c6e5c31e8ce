"""Mesh-sharded 3-way merge classification (VERDICT r3 next-step #7).

Blocks are partitioned host-side by ``key % n_shards`` — block-cyclic over
PK-space, the device analog of kart's PathEncoder modulus sharding
(`kart/dataset3_paths.py:283-299`): a key lands on
the same shard in all three revisions, so every per-key 3-way decision is
fully shard-local and only the (conflicts, take_theirs) count vector
crosses the interconnect via ``psum``. Per-shard union key arrays are
computed host-side (the partitions are disjoint, so the global union is
the merge of per-shard unions) and results are reassembled into the global
sorted-union order the single-chip ``merge_classify`` contract promises.

Expressed with ``shard_map`` over the shared 1-D Mesh so the same program
runs on a real slice or the driver's virtual CPU mesh. (Reference analog:
the per-feature 3-way rules of kart/merge_util.py applied via libgit2's
tree merge — here the whole key space classifies at once, SPMD over the
feature axis, the same fan-out shape as the reference's N-process import,
kart/fast_import.py:286-399.)
"""

import functools

import numpy as np

from kart_tpu.ops.blocks import PAD_KEY, bucket_size
from kart_tpu.parallel.mesh import FEATURES_AXIS
from kart_tpu.parallel.sharded_diff import STATS


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


def _sharded_merge_step(
    a_keys, a_oids, a_counts,
    o_keys, o_oids, o_counts,
    t_keys, t_oids, t_counts,
    u_keys, u_counts,
):
    """shard_map body: per-device slices (1, B[, 5]) / (1, U). The classify
    core is the exact single-chip traceable core; counts psum over the
    mesh."""
    import jax
    import jax.numpy as jnp

    from kart_tpu.ops.merge_kernel import _merge_classify_padded_core

    decision, presence, n_conf, n_theirs = _merge_classify_padded_core(
        a_keys[0], a_oids[0], a_counts[0],
        o_keys[0], o_oids[0], o_counts[0],
        t_keys[0], t_oids[0], t_counts[0],
        u_keys[0], u_counts[0],
    )
    totals = jax.lax.psum(jnp.stack([n_conf, n_theirs]), FEATURES_AXIS)
    return decision[None], presence[None], totals


@functools.lru_cache(maxsize=8)
def make_sharded_merge(mesh):
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _sharded_merge_step,
        mesh=mesh,
        in_specs=(spec,) * 11,
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def sharded_merge_classify(ancestor_block, ours_block, theirs_block, mesh=None):
    """Drop-in for ``ops.merge_kernel.merge_classify`` with the classify
    running shard-local on every device of ``mesh``: -> (union (U,) int64,
    decision (U,) int8, presence (U,) int8, stats dict), in global sorted
    union order — identical output to the single-chip path (tested)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.parallel.mesh import make_mesh

    if mesh is None:
        mesh = make_mesh()
    n_shards = mesh.devices.size
    parts = [
        partition_block(b, n_shards)
        for b in (ancestor_block, ours_block, theirs_block)
    ]
    bucket = max(p[0].shape[1] for p in parts)
    parts = [_repad(p, bucket) for p in parts]

    # per-shard unions (host): partitions are key-disjoint, so the global
    # union is exactly the concatenation of these
    unions = []
    for s in range(n_shards):
        u = np.union1d(
            np.union1d(
                parts[0][0][s][: parts[0][2][s]],
                parts[1][0][s][: parts[1][2][s]],
            ),
            parts[2][0][s][: parts[2][2][s]],
        )
        unions.append(u.astype(np.int64))
    u_bucket = bucket_size(max(max((len(u) for u in unions), default=1), 1), 256)
    union_mat = np.full((n_shards, u_bucket), PAD_KEY, dtype=np.int64)
    u_counts = np.zeros(n_shards, dtype=np.int32)
    for s, u in enumerate(unions):
        union_mat[s, : len(u)] = u
        u_counts[s] = len(u)

    fn = make_sharded_merge(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    args = []
    for p in parts:
        args.extend(
            (
                jax.device_put(p[0], sharding),
                jax.device_put(p[1], sharding),
                jax.device_put(p[2], sharding),
            )
        )
    args.append(jax.device_put(union_mat, sharding))
    args.append(jax.device_put(u_counts, sharding))
    decision_p, presence_p, totals = fn(*args)
    STATS["sharded_merge_calls"] = STATS.get("sharded_merge_calls", 0) + 1

    decision_p = np.asarray(decision_p)
    presence_p = np.asarray(presence_p)
    # reassemble global sorted order: concat per-shard slices, sort by key
    union_cat = np.concatenate(unions) if unions else np.zeros(0, np.int64)
    dec_cat = np.concatenate(
        [decision_p[s, : u_counts[s]] for s in range(n_shards)]
    ) if n_shards else np.zeros(0, np.int8)
    pres_cat = np.concatenate(
        [presence_p[s, : u_counts[s]] for s in range(n_shards)]
    ) if n_shards else np.zeros(0, np.int8)
    order = np.argsort(union_cat, kind="stable")
    union = union_cat[order]
    decision = dec_cat[order]
    presence = pres_cat[order]
    totals = np.asarray(totals)
    return (
        union,
        decision,
        presence,
        {"conflicts": int(totals[0]), "take_theirs": int(totals[1])},
    )
