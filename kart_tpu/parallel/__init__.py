"""Scale-out layer: the device mesh and the mesh-sharded 3-way merge with
its block-cyclic PK sharding (SURVEY.md §2.3, §7 step 7). The mesh diff is
``kart_tpu/diff/device_batch.py`` (key-range record batches).

The reference scales with process fan-out (N `git fast-import` workers,
`kart/fast_import.py:286-399`) and its "network" is the git smart protocol.
Here the same roles are played by a `jax.sharding.Mesh`: the merge's blocks are
partitioned over devices by PK modulus (the same invariant kart's PathEncoder
uses to spread features over subtrees — `kart/dataset3_paths.py:283-299`), so
every device owns a deterministic slice of PK-space in *every* revision and
all its joins are shard-local; only the scalar counts cross the ICI
via `psum`.
"""

from kart_tpu.parallel.mesh import make_mesh, best_device_count

__all__ = ["make_mesh", "best_device_count"]
