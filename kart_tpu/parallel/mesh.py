"""Device mesh construction.

One logical axis, ``"features"``: the framework's unit of parallelism is the
PK-space partition (reference analog: the feature-subtree shard key of the
parallel importer, `kart/fast_import.py:333-337`). Meshes are 1-D because the
workload is embarrassingly shard-local once the keys are cut into key-range
record batches; a second axis buys nothing until multi-host DCN topologies (where the axis
would split into ("host", "device")).
"""

import numpy as np

FEATURES_AXIS = "features"

# jax imported inside functions: this module sits on the small-diff CLI path
# (via parallel.__init__ / sharded_diff routing) which must not pay a jax
# import when it never touches the mesh.


def best_device_count(limit=None):
    """Device count for a new mesh: all visible devices (optionally capped).
    The record batches pad each shard slot alike, so any shard count works."""
    import jax

    n = jax.device_count()
    if limit is not None:
        n = min(n, limit)
    return n


def make_mesh(n_devices=None, devices=None):
    """An ``n_devices``-device 1-D mesh over the ``"features"`` axis."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        if n_devices is None:
            n_devices = best_device_count()
        devices = jax.devices()[:n_devices]
    return Mesh(np.asarray(devices), (FEATURES_AXIS,))
