"""Scale-out layer: the device mesh (SURVEY.md §2.3, §7 step 7). The mesh
diff is ``kart_tpu/diff/device_batch.py`` (key-range record batches under
``shard_map``), and a merge on the mesh is that diff twice
(``diff/backend.py merge_classify``).

The reference scales with process fan-out (N `git fast-import` workers,
`kart/fast_import.py:286-399`) and its "network" is the git smart protocol.
Here the same roles are played by a `jax.sharding.Mesh`: a diff's blocks are
cut into key-range record batches dealt over the devices, so every batch
holds the same key range of both revisions and all its joins are
shard-local; only the count vector crosses the ICI via `psum`.
"""

from kart_tpu.parallel.mesh import make_mesh, best_device_count

__all__ = ["make_mesh", "best_device_count"]
