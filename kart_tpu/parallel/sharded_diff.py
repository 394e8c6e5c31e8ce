"""What the mesh paths share with their callers: the call counters and a
synthetic block maker (``chip_smoke.py``, ``bench.py``, the benchmark's
rehearsals and the driver's ``dryrun_multichip`` import both from here).

The mesh diff itself is :mod:`kart_tpu.diff.device_batch` (key-range record
batches under ``shard_map``); a merge on the mesh is that diff twice
(:func:`kart_tpu.diff.backend.merge_classify`), so it counts two
``sharded_classify_calls``.
"""

import numpy as np

from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock, bucket_size

# observability: how many times the mesh path actually ran this process
# (dryrun_multichip and tests assert on it — the single-chip path silently
# taking over would otherwise be invisible)
STATS = {"sharded_classify_calls": 0}


def synthetic_block(n, seed=0, change_none=False):
    """Synthetic FeatureBlock for benchmarks/dryruns: keys 0..n-1 with random
    oids (deterministic per seed)."""
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    size = bucket_size(max(n, 1))
    if size > n:
        keys = np.concatenate([keys, np.full(size - n, PAD_KEY, dtype=np.int64)])
        oids = np.concatenate([oids, np.zeros((size - n, 5), dtype=np.uint32)])
    return FeatureBlock(keys, oids, None, n)  # benchmarks never materialise values
