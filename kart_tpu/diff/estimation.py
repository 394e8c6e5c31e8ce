"""Sampled diff feature-count estimation (reference: kart/diff_estimation.py
+ the subtree-sampling machinery in kart/dataset3_paths.py:217-424).

The feature path encoder spreads features uniformly over a fixed tree fanout
(64-branch x 4-level for int PKs), so the top-level branches of a feature
tree are ~equal-size random partitions of PK space.  That makes diff-count
estimation O(samples) instead of O(n): exact-count a few *differing*
branches, then extrapolate by the number of differing branches.

Accuracy levels match the reference (diff_estimation.py:8-13):
veryfast=2 / fast=16 / medium=32 / good=64 sampled subtrees, or ``exact``.
Results are memoised in the annotations DB, keyed by the tree pair and
accuracy, exactly like the reference caches them (diff_estimation.py:117-124).

Two engines, chosen per dataset:

* **Tree sampling** (host): exact-count sampled *differing* top branches,
  extrapolate — O(samples) odb reads, no columnar data needed.
* **Device-sharded column sampling**: when both revisions carry columnar
  sidecars, sample ``samples`` of 64 block-cyclic pk-residue classes (the
  same modulus invariant the PathEncoder / mesh partitioner use), classify
  just those rows shard-local over the device mesh, psum the count vector,
  and scale — the SURVEY §2.3 "pmap'd sampled reduction" slot, one
  partition class per device.
"""

import numpy as np

ACCURACY_SUBTREE_SAMPLES = {
    "veryfast": 2,
    "fast": 16,
    "medium": 32,
    "good": 64,
}
ACCURACY_CHOICES = (*ACCURACY_SUBTREE_SAMPLES, "exact")

# the modulus partition count for column sampling; matches the path
# encoder's top fanout so a "sample" has the same granularity as one
# sampled tree branch
SAMPLE_PARTITIONS = 64


def estimate_diff_feature_counts(
    repo, base_rs, target_rs, *, accuracy="fast", use_annotations=True,
    ds_paths=None,
):
    """-> {ds_path: estimated changed-feature count} between two revisions.
    Counts are exact whenever that's as cheap (small diffs, equal trees)."""
    if accuracy not in ACCURACY_CHOICES:
        raise ValueError(
            f"accuracy must be one of {', '.join(ACCURACY_CHOICES)}"
        )
    annotations = None
    if use_annotations:
        from kart_tpu.annotations import DiffAnnotations

        annotations = DiffAnnotations(repo)
        base_tree = base_rs.tree_oid if base_rs else None
        target_tree = target_rs.tree_oid if target_rs else None
        cached = annotations.get(
            base_tree, target_tree, f"feature-change-counts-{accuracy}"
        )
        if cached is not None:
            # the cache always holds *full* counts; subset for filtered calls
            if ds_paths is not None:
                return {p: c for p, c in cached.items() if p in ds_paths}
            return cached

    base_datasets = base_rs.datasets if base_rs else {}
    target_datasets = target_rs.datasets if target_rs else {}
    base_paths = set(base_datasets.paths()) if base_rs else set()
    target_paths = set(target_datasets.paths()) if target_rs else set()

    counts = {}
    wanted = sorted(base_paths | target_paths)
    if ds_paths is not None:
        wanted = [p for p in wanted if p in ds_paths]
    for ds_path in wanted:
        old_ds = base_datasets.get(ds_path) if base_rs else None
        new_ds = target_datasets.get(ds_path) if target_rs else None
        count = None
        if accuracy != "exact":
            count = _estimate_columnar(repo, old_ds, new_ds, accuracy)
        if count is None:
            old_tree = old_ds.feature_tree if old_ds else None
            new_tree = new_ds.feature_tree if new_ds else None
            count = _estimate_tree_pair(repo.odb, old_tree, new_tree, accuracy)
        if count:
            counts[ds_path] = count

    # only full runs populate the cache — a filtered subset under the
    # unfiltered key would poison later unfiltered reads
    if annotations is not None and ds_paths is None:
        annotations.set(
            base_tree, target_tree, counts, f"feature-change-counts-{accuracy}"
        )
    return counts


# below this row count the host tree walk beats any columnar dispatch — the
# sampling machinery only pays off when slicing columns saves real work
COLUMNAR_ESTIMATE_MIN_ROWS = 100_000


def _estimate_columnar(repo, old_ds, new_ds, accuracy):
    """Column-sampled estimate from the sidecars, or None when they aren't
    available / worthwhile (caller falls back to the host tree walk)."""
    if old_ds is None or new_ds is None or repo is None:
        return None
    old_tree = old_ds.feature_tree
    new_tree = new_ds.feature_tree
    if (old_tree.oid if old_tree is not None else None) == (
        new_tree.oid if new_tree is not None else None
    ):
        return 0  # unchanged dataset: never touch the sidecars
    for ds in (old_ds, new_ds):
        enc = getattr(ds, "path_encoder", None)
        if enc is None or enc.scheme != "int":
            return None  # hash keys: residues of the hash aren't pk classes
    from kart_tpu.diff import sidecar

    if not (
        sidecar.has_sidecar(repo, old_ds) and sidecar.has_sidecar(repo, new_ds)
    ):
        return None
    old_block = sidecar.load_block(repo, old_ds)
    new_block = sidecar.load_block(repo, new_ds)
    if old_block is None or new_block is None:
        return None
    if max(old_block.count, new_block.count) < COLUMNAR_ESTIMATE_MIN_ROWS:
        return None
    return estimate_counts_from_blocks(old_block, new_block, accuracy)


def estimate_counts_from_blocks(old_block, new_block, accuracy):
    """Sampled changed-feature count from two (pk, oid) column blocks.

    Samples ``samples`` of SAMPLE_PARTITIONS partition classes of a *mixed*
    key hash (a fixed multiply/shift bijection — raw ``pk % 64`` would alias
    with strided pk allocations like all-even fids, under- or over-counting
    by a constant factor). On a multi-device mesh each device classifies its
    own slice of the sample and only the 3-scalar count vector is psum'd
    (SURVEY §2.3's sampled reduction). Scaling by partitions/samples makes
    the estimate unbiased: mixed classes are ~equal pseudo-random partitions
    of pk space, like the path encoder's hash subtrees."""
    samples = ACCURACY_SUBTREE_SAMPLES[accuracy]
    k = min(samples, SAMPLE_PARTITIONS)

    def partition_class(keys):
        # splitmix-style mixer: identical for both sides of the diff, so a
        # pk lands in the same class in every revision
        h = keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        return (h >> np.uint64(58)) % np.uint64(SAMPLE_PARTITIONS)

    def subsample(block):
        from kart_tpu.ops.blocks import FeatureBlock, PAD_KEY, bucket_size

        keys = block.keys[: block.count]
        mask = partition_class(keys) < k
        sub_keys = keys[mask]
        sub_oids = block.oids[: block.count][mask]
        n = len(sub_keys)
        size = bucket_size(max(n, 1))
        keys_p = np.full(size, PAD_KEY, dtype=np.int64)
        keys_p[:n] = sub_keys
        oids_p = np.zeros((size, 5), dtype=np.uint32)
        oids_p[:n] = sub_oids
        return FeatureBlock(keys_p, oids_p, None, n)

    old_sub = subsample(old_block)
    new_sub = subsample(new_block)

    # backend seam: on the sharded backend the sampled count runs as a
    # pmapped psum reduction — each device classifies its key-range slice
    # of the subsample and only the 3-scalar count vector comes home
    from kart_tpu.diff.backend import select_backend

    counts = select_backend(max(old_sub.count, new_sub.count)).sampled_counts(
        old_sub, new_sub
    )
    total = counts["inserts"] + counts["updates"] + counts["deletes"]
    if k == SAMPLE_PARTITIONS:
        return total  # sampled everything: exact
    return round(total * SAMPLE_PARTITIONS / k)


def _estimate_tree_pair(odb, old_tree, new_tree, accuracy):
    old_oid = old_tree.oid if old_tree is not None else None
    new_oid = new_tree.oid if new_tree is not None else None
    if old_oid == new_oid:
        return 0
    if accuracy == "exact":
        return _count_tree_diff(odb, old_oid, new_oid)

    samples = ACCURACY_SUBTREE_SAMPLES[accuracy]
    old_entries = _entry_map(odb, old_oid)
    new_entries = _entry_map(odb, new_oid)
    differing = sorted(
        name
        for name in set(old_entries) | set(new_entries)
        if old_entries.get(name) != new_entries.get(name)
    )
    if len(differing) <= samples:
        # cheaper to be exact: every non-differing branch contributes 0
        return sum(
            _count_tree_diff(odb, old_entries.get(n), new_entries.get(n))
            for n in differing
        )

    # evenly-spaced deterministic sample of the differing branches (branch
    # content is hash-distributed, so spacing is as good as randomness and
    # reproducible across runs)
    step = len(differing) / samples
    sampled = [differing[int(i * step)] for i in range(samples)]
    total = sum(
        _count_tree_diff(odb, old_entries.get(n), new_entries.get(n))
        for n in sampled
    )
    return round(total / samples * len(differing))


def _entry_map(odb, tree_oid):
    """tree oid -> {entry name: (oid, is_tree)}; {} for None."""
    if tree_oid is None:
        return {}
    return {e.name: (e.oid, e.is_tree) for e in odb.read_tree_entries(tree_oid)}


def _count_tree_diff(odb, old, new):
    """Exact count of differing blob paths between two (sub)tree values.
    Accepts oids, (oid, is_tree) entry tuples, or None."""
    old_oid, old_is_tree = _normalise(old)
    new_oid, new_is_tree = _normalise(new)
    if old_oid == new_oid and old_is_tree == new_is_tree:
        return 0
    if old_oid is None:
        return _count_blobs(odb, new_oid, new_is_tree)
    if new_oid is None:
        return _count_blobs(odb, old_oid, old_is_tree)
    if not old_is_tree and not new_is_tree:
        return 1  # two different blobs at the same path: one modified feature
    if old_is_tree != new_is_tree:
        return _count_blobs(odb, old_oid, old_is_tree) + _count_blobs(
            odb, new_oid, new_is_tree
        )
    old_entries = _entry_map(odb, old_oid)
    new_entries = _entry_map(odb, new_oid)
    return sum(
        _count_tree_diff(odb, old_entries.get(n), new_entries.get(n))
        for n in set(old_entries) | set(new_entries)
        if old_entries.get(n) != new_entries.get(n)
    )


def _normalise(value):
    if value is None:
        return None, False
    if isinstance(value, tuple):
        return value
    return value, True  # bare oid: tree by construction


def _count_blobs(odb, oid, is_tree):
    if not is_tree:
        return 1
    count = 0
    for e in odb.read_tree_entries(oid):
        count += _count_blobs(odb, e.oid, e.is_tree)
    return count
