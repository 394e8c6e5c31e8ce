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
no point that lies within ``EDGE_CLEARANCE`` degrees of a filter edge is
edited.

The edit set is stratified, so that every seed gives the filtered count the
same work: each row falls in one stratum by four marks — its envelope meets
the filter's bounding box, its point lies inside the polygon, its census
block (``census_block_rows`` consecutive rows, the sidecar's aggregate
block) straddles the padded box, its padded envelope is crossed by the
polygon's edge (so the count reads its blob) — and a seed draws a fixed
number of rows from each stratum, uniformly: the expected counts of a
uniform draw, which at the configuration's size are its ``edit_strata``
counts. The strata are seed-free and kept with the base.
"""

import importlib.util
import json
import os

import numpy as np

EDGE_CLEARANCE = 1e-6  # degrees
#: marks of a row's stratum (:func:`row_strata`); NEVER: never edited
IN_BOX, IN_POLYGON, BOUNDARY_BLOCK, ON_EDGE = 1, 2, 4, 8
NEVER = 255
MARKS = {IN_BOX: "in_box", IN_POLYGON: "in_polygon",
         BOUNDARY_BLOCK: "boundary_block", ON_EDGE: "on_edge"}
STRATA_FILE = "edit_strata.npy"


def _sibling(name):
    """benchmarks/layers/<name>.py, loaded as run.py loads a builder."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_layers_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base_layer = _sibling("int_pk_layer")
PK_BASE = base_layer.PK_BASE


def build_base(path, params):
    """:func:`int_pk_layer.build_base`, and every row's stratum beside it."""
    base_layer.build_base(path, params)
    np.save(os.path.join(path, STRATA_FILE), row_strata(params))


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


def crosses_edge(ring, x0, x1, y0, y1):
    """bool per rectangle: does a segment of ``ring`` meet it? (Liang-Barsky
    clipping of each segment to each rectangle.)"""
    hit = np.zeros(len(x0), dtype=bool)
    for (ax, ay), (bx, by) in zip(ring[:-1], ring[1:]):
        near = np.flatnonzero(
            (x1 >= min(ax, bx)) & (x0 <= max(ax, bx))
            & (y1 >= min(ay, by)) & (y0 <= max(ay, by))
        )
        lo, hi = np.zeros(len(near)), np.ones(len(near))
        inside = np.ones(len(near), dtype=bool)
        for d, a, low, high in ((bx - ax, ax, x0[near], x1[near]),
                                (by - ay, ay, y0[near], y1[near])):
            if d == 0:
                inside &= (a >= low) & (a <= high)
                continue
            t0, t1 = (low - a) / d, (high - a) / d
            lo = np.maximum(lo, np.minimum(t0, t1))
            hi = np.minimum(hi, np.maximum(t0, t1))
        hit[near[inside & (lo <= hi)]] = True
    return hit


def row_strata(params):
    """uint8 per row of the layer: the OR of its MARKS (NEVER for a point
    within EDGE_CLEARANCE of an edge). The box is the filter's bounding box
    as ``n_edits_in_box`` counts it; a census block is boundary where its
    rows' envelopes, aggregated, meet the box padded by the strata's ``pad``
    without lying inside it; an envelope padded by ``pad`` is on the edge
    where a segment of the ring meets it (the count can then decide it only
    by its blob)."""
    n, geometry = params["rows"], params["geometry"]
    strata = params["edit_strata"]
    block, pad = strata["census_block_rows"], strata["pad"]
    ring = filter_ring(params)
    x, y = base_layer.origins(geometry, PK_BASE + np.arange(n, dtype=np.int64), n)
    env = base_layer.envelopes(x, y)
    w, s, e, north = ring[:, 0].min(), ring[:, 1].min(), ring[:, 0].max(), ring[:, 1].max()
    label = np.where(
        (env[:, 2] >= w) & (x <= e) & (env[:, 3] >= s) & (y <= north), IN_BOX, 0
    ).astype(np.uint8)
    box = np.flatnonzero(label)
    label[box[points_in_ring(ring, x[box], y[box])]] |= IN_POLYGON
    tail = np.repeat(env[-1:], (-n) % block, axis=0)
    blocks = np.concatenate([env, tail]).reshape(-1, block, 4)
    bw, bs = blocks[:, :, 0].min(axis=1), blocks[:, :, 1].min(axis=1)
    be, bn = blocks[:, :, 2].max(axis=1), blocks[:, :, 3].max(axis=1)
    meets = (be >= w - pad) & (bw <= e + pad) & (bn >= s - pad) & (bs <= north + pad)
    within = (bw >= w - pad) & (be <= e + pad) & (bs >= s - pad) & (bn <= north + pad)
    label |= np.repeat(np.where(meets & ~within, BOUNDARY_BLOCK, 0), block)[:n].astype(
        np.uint8
    )
    env = env[box].astype(np.float64)
    label[box[crosses_edge(ring, env[:, 0] - pad, env[:, 2] + pad,
                           env[:, 1] - pad, env[:, 3] + pad)]] |= ON_EDGE
    # a point within the clearance of a segment lies in the square of that
    # half-side about it which the segment meets
    c = EDGE_CLEARANCE
    near = np.flatnonzero((x >= w - c) & (x <= e + c) & (y >= s - c) & (y <= north + c))
    near = near[crosses_edge(ring, x[near] - c, x[near] + c, y[near] - c, y[near] + c)]
    label[near[edge_distance(ring, x[near], y[near]) < c]] = NEVER
    return label


def points_in_ring(ring, x, y):
    """bool per point: inside the closed ring, by the even-odd rule."""
    inside = np.zeros(len(x), dtype=bool)
    for (ax, ay), (bx, by) in zip(ring[:-1], ring[1:]):
        if ay != by:
            straddles = (ay > y) != (by > y)
            inside ^= straddles & (x < ax + (y - ay) * (bx - ax) / (by - ay))
    return inside


def stratum_name(label):
    """``in_box,in_polygon,...`` for a label, ``elsewhere`` for none."""
    return ",".join(name for bit, name in MARKS.items() if label & bit) or "elsewhere"


def drawable(strata):
    """The labels of the strata that hold a row that may be edited."""
    return np.flatnonzero(np.bincount(strata, minlength=NEVER)[:NEVER])


def expected_counts(strata, n_edits):
    """{stratum name: edits} of a uniform draw of ``n_edits`` rows, in
    expectation: each stratum's share of the rows that may be edited, the
    remainders given to the largest fractions."""
    labels = drawable(strata)
    pops = np.bincount(strata, minlength=NEVER)[labels]
    exact = n_edits * pops / pops.sum()
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(counts - exact, kind="stable")[: n_edits - counts.sum()]] += 1
    return {stratum_name(label): int(c) for label, c in zip(labels, counts)}


def edit_counts(params, strata):
    """{stratum name: edits} a seed draws: the expected counts of a uniform
    draw of ``edit_frac`` of the rows. At the configuration's own size they
    are the ``edit_strata`` counts it states, or the layer is not the one
    the configuration describes."""
    n_edits = max(1, int(params["rows"] * params["edit_frac"]))
    counts = expected_counts(strata, n_edits)
    stated = params["edit_strata"]
    if params["rows"] == stated["rows"] and counts != stated["counts"]:
        raise ValueError(f"edit_strata counts {stated['counts']} are not the "
                         f"layer's expected counts {counts}")
    return counts


def edit_rows(params, seed, strata=None):
    """The row numbers the edit commit of ``seed`` rewrites, sorted: from
    each stratum (:func:`row_strata`) its count of rows (:func:`edit_counts`),
    uniform without replacement."""
    if strata is None:
        strata = row_strata(params)
    names = {stratum_name(label): label for label in drawable(strata)}
    rng = np.random.default_rng(seed)
    picked = [
        rng.choice(np.flatnonzero(strata == names[name]), size=count, replace=False)
        for name, count in sorted(edit_counts(params, strata).items()) if count
    ]
    return np.sort(np.concatenate(picked))


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
    strata = np.load(os.path.join(base, STRATA_FILE))
    rows = edit_rows(params, seed, strata)
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
    marks = strata[rows]
    info = {"edit_pks": pks[rows], "edit_xy": np.stack([x, y], axis=1),
            "n_edits": len(rows)}
    for bit, name in MARKS.items():
        info[f"n_edits_{name}"] = int(np.count_nonzero(marks & bit))
    return path, info
