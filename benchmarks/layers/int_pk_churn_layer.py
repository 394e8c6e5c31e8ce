"""Layer builder ``int_pk_churn_layer``: ``int_pk_layer``'s dataset, republished.

The base is :func:`int_pk_layer.build_base` itself (the import commit:
``rows`` features, pk ``PK_BASE + i``, ``rating = pk / 2``; seed-free, built
once a checkout). What is new is the edit: a republish of the authoritative
table changes the key set. From ``--seed``, :func:`add_edit_commit` writes
**two** commits into the run's thin repository, each a child of the import
commit, on branches of their own (``HEAD`` stays on the import commit, so a
cell's command is ``kart diff HEAD...<branch>``):

* ``churn`` — ``update_frac`` of the rows get ``rating = pk`` (the founding
  edit), ``delete_frac`` are deleted, both chosen uniformly without
  replacement and disjoint; ``insert_frac`` new rows are appended with the
  next serial ids ``PK_BASE + rows ...`` (a serial pk never fills a hole),
  ``rating = pk / 2`` and the geometry of their pk in a layer grown by them.
* ``bulk`` — one contiguous run of ``bulk_delete_frac`` of the pks deleted
  (rows loaded together, so serial ids in one run), its start uniform over
  the starts in the middle 80% of the key range at which the run lies
  strictly inside one aligned block of ``bulk_block_rows`` rows (so every seed's hole
  lies within one key-range chunk of the one-chip classify, whose size the
  configuration states); ``update_frac`` of the rows elsewhere rewritten.

Every count is ``int(rows * frac)``, never drawn, so array shapes repeat
across seeds. A commit that changes the key set has a feature tree and a
sidecar of its own: both are made whole from the new side's (pk, oid)
columns, by the program's own tree builder and sidecar writer.
"""

import importlib.util
import json
import os

import numpy as np


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


def _count(params, key):
    return int(params["rows"] * params[key])


def churn_edits(rng, params):
    """Uniform updates and deletes, disjoint; appended inserts."""
    n_upd, n_del = _count(params, "update_frac"), _count(params, "delete_frac")
    picked = rng.choice(params["rows"], size=n_upd + n_del, replace=False)
    return (
        np.sort(picked[:n_upd]), np.sort(picked[n_upd:]),
        _count(params, "insert_frac"),
    )


def bulk_starts(params):
    """Where the bulk run may start: -> (first start, number of starts) of
    each aligned block of ``bulk_block_rows`` rows that can hold the whole
    run strictly inside it (a run on a block's edge leaves the block's kept
    rows one contiguous range, as a run across its edge does), and inside
    the middle 80% of the rows."""
    n, block = params["rows"], params["bulk_block_rows"]
    n_del = _count(params, "bulk_delete_frac")
    lo, hi = n // 10, n - n // 10 - n_del  # the run lies in the middle 80%
    block_lo = np.arange(0, n, block, dtype=np.int64)
    first = np.maximum(block_lo + 1, lo)
    last = np.minimum(block_lo + block - n_del - 1, hi)
    fits = last >= first
    return first[fits], (last - first + 1)[fits]


def bulk_edits(rng, params):
    """One contiguous run deleted, strictly inside one aligned block of
    ``bulk_block_rows`` rows, its start uniform over all such starts;
    uniform updates outside it."""
    n = params["rows"]
    n_del = _count(params, "bulk_delete_frac")
    first, counts = bulk_starts(params)
    at = int(rng.integers(int(counts.sum())))
    block = int(np.searchsorted(np.cumsum(counts), at, side="right"))
    start = int(first[block] + at - (counts[:block].sum()))
    deleted = np.arange(start, start + n_del, dtype=np.int64)
    # an index into the rows outside the run, stepped over it
    outside = rng.choice(n - n_del, size=_count(params, "update_frac"), replace=False)
    updated = np.sort(np.where(outside < start, outside, outside + n_del))
    return updated, deleted, 0


#: branch -> its edit sets from (rng, params), drawn in this order
EDITS = {"churn": churn_edits, "bulk": bulk_edits}
BRANCHES = tuple(EDITS)


def edit_sets(params, seed):
    """{branch: (updated rows, deleted rows, inserted count)} of ``seed``:
    row numbers of the base layer, sorted. The two branches draw from one
    generator."""
    rng = np.random.default_rng(seed)
    return {branch: draw(rng, params) for branch, draw in EDITS.items()}


def key_columns(params, edits):
    """What one commit's edit sets do to the key column: -> (the base
    layer's pks, the mask of those that stay, the appended pks). The new
    side's sorted keys are the kept base pks, then the appended ones."""
    _, deleted, n_inserted = edits
    n = params["rows"]
    keep = np.ones(n, dtype=bool)
    keep[deleted] = False
    return (
        PK_BASE + np.arange(n, dtype=np.int64), keep,
        PK_BASE + n + np.arange(n_inserted, dtype=np.int64),
    )


def _commit(repo, meta, params, branch, base_oids, edits):
    """Write one republish commit on ``refs/heads/<branch>``; -> its info."""
    from kart_tpu.core.feature_tree import build_int_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3

    updated, deleted, n_inserted = edits
    n, geometry = params["rows"], params["geometry"]
    base_pks, keep, inserted_pks = key_columns(params, edits)
    odb = repo.odb
    with odb.bulk_pack(level=0):
        oids = base_oids.copy()
        oids[updated] = base_layer.write_blobs(
            odb, geometry, base_pks[updated],
            base_layer.new_rating(base_pks[updated]), n,
        )
        # the appended points lie where the layer, grown by them, puts them
        # (inside the lon/lat range, which `rows` = n would leave)
        inserted_oids = base_layer.write_blobs(
            odb, geometry, inserted_pks, base_layer.old_rating(inserted_pks),
            n + n_inserted,
        )
        pks = np.concatenate([base_pks[keep], inserted_pks])
        oids = np.concatenate([oids[keep], inserted_oids])
        ftree = build_int_feature_tree(odb, pks, oids)
        tb = TreeBuilder(odb, meta["root"])
        tb.insert(f"{base_layer.DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree,
                  mode=MODE_TREE)
        root = tb.flush()
    commit = repo.create_commit(
        f"refs/heads/{branch}", root, f"republish: {branch}", [meta["commit"]]
    )
    envelopes = None
    if params.get("envelopes"):
        envelopes = np.concatenate([
            base_layer.envelopes(*base_layer.origins(geometry, part, rows))
            for part, rows in (
                (base_pks[keep], n), (inserted_pks, n + n_inserted)
            )
        ])
    sidecar.save_sidecar(repo, ftree, pks, oids, envelopes=envelopes)
    return {
        "commit": commit,
        "rows": len(pks),
        "updated_pks": base_pks[updated],
        "deleted_pks": base_pks[deleted],
        "inserted_pks": inserted_pks,
        "n_edits": len(updated) + len(deleted) + n_inserted,
    }


def add_edit_commit(base, work, params, seed):
    """The run's repository: a thin one at ``work/repo`` over the base's
    objects, ``HEAD`` on the import commit, and the two republish commits of
    ``seed`` on the branches ``churn`` and ``bulk``.
    -> (repo path, {"commits": {branch: {"commit", "rows", "updated_pks",
    "deleted_pks", "inserted_pks" (sorted int64), "n_edits"}}, "n_edits":
    the two commits' together})."""
    from kart_tpu.core.repo import KartRepo

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

    base_oids = np.load(os.path.join(base, "oids.npy"))
    commits = {
        branch: _commit(repo, meta, params, branch, base_oids, edits)
        for branch, edits in edit_sets(params, seed).items()
    }
    return path, {
        "commits": commits,
        "n_edits": sum(c["n_edits"] for c in commits.values()),
    }
