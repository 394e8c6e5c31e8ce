"""Layer builder ``nodes_filtered_layer``: ``int_pk_layer``'s point dataset in
a repository with a polygonal spatial filter set.

The base is :func:`int_pk_layer.build_base` itself (the import commit:
``rows`` points laid out as an OSM-nodes import lays them, every blob in the
pack, the envelope column in the sidecar; seed-free, built once a checkout).
:func:`add_edit_commit` makes the founding edit from ``--seed`` (``edit_frac``
of the rows, uniform over the whole layer, ``rating = pk``) and then writes
the configuration's filter into the run's repository the way ``kart checkout
--spatial-filter`` does (``ResolvedSpatialFilterSpec.config_items()``). No
blob is left out: a full repository with the filter set, not a partial clone.

The filter is the configuration's (``params["filter"]``), not the seed's.
What the seed decides is which rows change, and the reference
(``references/feature_count_filtered.py``) counts the changed points inside
the polygon by itself, in float64. So that rounding cannot separate the two,
an edited point that lies within ``EDGE_CLEARANCE`` degrees of a filter edge
is drawn again.
"""

import importlib.util
import json
import os

import numpy as np

EDGE_CLEARANCE = 1e-6  # degrees


def _sibling(name):
    """benchmarks/layers/<name>.py, loaded as run.py loads a builder."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_layers_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base_layer = _sibling("int_pk_layer")
PK_BASE = base_layer.PK_BASE
build_base = base_layer.build_base


def filter_ring(params):
    """The filter's outer ring, closed: (49, 2) float64 lon, lat."""
    ring = np.asarray(params["filter"]["ring"], dtype=np.float64)
    assert len(ring) == params["filter"]["vertices"] + 1 and (ring[0] == ring[-1]).all()
    return ring


def filter_spec_string(params):
    """``<crs>;POLYGON((...))`` as `kart checkout --spatial-filter` takes it."""
    ring = ", ".join(f"{x!r} {y!r}" for x, y in filter_ring(params).tolist())
    return f"{params['filter']['crs']};POLYGON(({ring}))"


def edge_distance(ring, x, y):
    """Least distance, in degrees, from each point to the ring's segments."""
    least = np.full(len(x), np.inf)
    for (ax, ay), (bx, by) in zip(ring[:-1], ring[1:]):
        dx, dy = bx - ax, by - ay
        t = np.clip(((x - ax) * dx + (y - ay) * dy) / (dx * dx + dy * dy), 0.0, 1.0)
        least = np.minimum(least, np.hypot(x - (ax + t * dx), y - (ay + t * dy)))
    return least


def edit_rows(params, seed):
    """The row numbers the edit commit of ``seed`` rewrites, sorted: the
    founding draw (uniform, without replacement), a row whose point is
    within EDGE_CLEARANCE of a filter edge replaced by a fresh draw."""
    n = params["rows"]
    n_edits = max(1, int(n * params["edit_frac"]))
    rng = np.random.default_rng(seed)
    rows = rng.choice(n, size=n_edits, replace=False)
    ring = filter_ring(params)
    while True:
        x, y = base_layer.origins(params["geometry"], PK_BASE + rows, n)
        near = np.flatnonzero(edge_distance(ring, x, y) < EDGE_CLEARANCE)
        if not len(near):
            return np.sort(rows)
        free = np.setdiff1d(np.arange(n), rows)
        rows[near] = rng.choice(free, size=len(near), replace=False)


def add_edit_commit(base, work, params, seed):
    """The run's repository: a thin one at ``work/repo`` over the base's
    objects, the edit commit of ``seed`` on top, the filter in its
    configuration. -> (repo path, info): ``edit_pks`` (sorted int64),
    ``edit_xy`` (the float64 point of each), ``n_edits``, and
    ``n_edits_in_box``, the edits whose envelope meets the filter's
    bounding box (what a count by the envelope prefilter alone prints, give
    or take the prefilter's pad)."""
    from kart_tpu.core.feature_tree import emit_feature_tree, plan_int_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3
    from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

    with open(os.path.join(base, "base.json")) as f:
        meta = json.load(f)
    base_git = os.path.join(os.path.abspath(base), "repo", ".kart")
    path = os.path.join(work, "repo")
    repo = KartRepo.init_repository(path)
    repo.config.set_many({"user.name": "Bench", "user.email": "bench@example.com"})
    repo.odb.add_alternate(os.path.join(base_git, "objects"))
    repo.refs.set(meta["branch"], meta["commit"], "branch: base layer")
    columnar = os.path.join(repo.gitdir, "columnar")
    os.makedirs(columnar, exist_ok=True)
    os.symlink(os.path.join(base_git, "columnar", meta["sidecar"]),
               os.path.join(columnar, meta["sidecar"]))

    n, geometry = params["rows"], params["geometry"]
    pks = PK_BASE + np.arange(n, dtype=np.int64)
    rows = edit_rows(params, seed)
    oids = np.load(os.path.join(base, "oids.npy"))
    leaf_oids = [s.decode() for s in np.load(os.path.join(base, "leaf_oids.npy"))]
    odb = repo.odb
    with odb.bulk_pack(level=0):
        oids[rows] = base_layer.write_blobs(
            odb, geometry, pks[rows], base_layer.new_rating(pks[rows]), n
        )
        ftree, _ = emit_feature_tree(
            odb, plan_int_feature_tree(pks), oids, prev=(leaf_oids, rows)
        )
        tb = TreeBuilder(odb, meta["root"])
        tb.insert(f"{base_layer.DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree,
                  mode=MODE_TREE)
        root = tb.flush()
    repo.create_commit("HEAD", root, "edit", [meta["commit"]])
    sidecar.save_sidecar(
        repo, ftree, pks, oids,
        envelopes=base_layer.envelopes(*base_layer.origins(geometry, pks, n)),
    )
    repo.config.set_many(
        ResolvedSpatialFilterSpec.from_spec_string(
            filter_spec_string(params)
        ).config_items()
    )

    x, y = base_layer.origins(geometry, pks[rows], n)
    ring = filter_ring(params)
    box = base_layer.BOX  # a point's envelope is its corner plus BOX
    in_box = (
        (x + box >= ring[:, 0].min()) & (x <= ring[:, 0].max())
        & (y + box >= ring[:, 1].min()) & (y <= ring[:, 1].max())
    )
    return path, {
        "edit_pks": pks[rows],
        "edit_xy": np.stack([x, y], axis=1),
        "n_edits": len(rows),
        "n_edits_in_box": int(np.count_nonzero(in_box)),
    }
