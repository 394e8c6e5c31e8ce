"""Layer builder ``int_pk_merge_layer``: ``int_pk_layer``'s dataset on two
branches that have to be merged.

The base is :func:`int_pk_layer.build_base` itself (the import commit:
``rows`` features, pk ``PK_BASE + i``, ``rating = pk / 2``; seed-free, built
once a checkout) and is the merge's **ancestor**. From ``--seed``,
:func:`add_edit_commit` writes two commits into the run's thin repository,
each a child of the import commit: **ours** on the branch ``HEAD`` names,
**theirs** on ``refs/heads/theirs``, so that a cell's command is ``kart
merge theirs``. The rows of every kind are chosen uniformly without
replacement and are disjoint; every count is ``int(rows * frac)``, never
drawn, so array shapes repeat across seeds:

* ``edit_edit_frac`` — both branches rewrite ``rating``, ours to ``pk``,
  theirs to ``2 * pk``: a conflict with three versions;
* ``edit_delete_frac`` — ours rewrites, theirs deletes: a conflict whose
  theirs-version is absent;
* ``add_add_frac`` — both branches append the next serial ids
  ``PK_BASE + rows ...`` with different ratings (ours ``pk / 2``, theirs
  ``2 * pk``): a conflict with no ancestor;
* ``same_frac`` — both write ``rating = pk``, one blob: no conflict, ours kept;
* ``theirs_edit_frac`` / ``theirs_delete_frac`` / ``theirs_insert_frac`` —
  theirs alone rewrites (``2 * pk``), deletes, appends (the serial ids after
  the add/add ones, ``rating = pk / 2``): clean, taken;
* ``ours_edit_frac`` — ours alone rewrites (``pk``): clean, kept.

A commit that changes the key set has a feature tree and a sidecar of its
own: both are made whole from the branch's (pk, oid) columns, by the
program's own tree builder and sidecar writer. What the merge has to answer
is worked out here from the edit sets alone, with nothing of the merge
engine: the conflicting pks with their three versions, and the merged
(pk, oid) columns — ours' with theirs' clean changes —, which the reference
names as a feature tree with code of its own (no tree of the answer is
built here, so the run's repository holds none before the program writes
one).
"""

import importlib.util
import json
import os

import numpy as np

THEIRS = "theirs"

#: the kinds of rows drawn from the base layer, in drawing order
ROW_KINDS = (
    "edit_edit", "edit_delete", "same", "theirs_edit", "theirs_delete", "ours_edit",
)


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


def _count(params, kind):
    return int(params["rows"] * params[kind + "_frac"])


def theirs_rating(pks):
    return np.asarray(pks, dtype=np.float64) * 2.0


def edit_sets(params, seed):
    """{kind: sorted row numbers of the base layer} for :data:`ROW_KINDS`,
    disjoint, plus ``add_add`` and ``theirs_insert``: the appended pks."""
    n = params["rows"]
    counts = [_count(params, kind) for kind in ROW_KINDS]
    picked = np.random.default_rng(seed).choice(n, size=sum(counts), replace=False)
    sets, at = {}, 0
    for kind, count in zip(ROW_KINDS, counts):
        sets[kind] = np.sort(picked[at:at + count])
        at += count
    first = PK_BASE + n
    n_both, n_theirs = _count(params, "add_add"), _count(params, "theirs_insert")
    sets["add_add"] = first + np.arange(n_both, dtype=np.int64)
    sets["theirs_insert"] = first + n_both + np.arange(n_theirs, dtype=np.int64)
    return sets


def branch_columns(params, sets, base_oids, write):
    """The two branches' (pks, oids) columns, sorted by pk. ``write(pks,
    ratings) -> (n, 20) uint8`` makes the blobs and names them.
    -> {"ours": (pks, oids), "theirs": (pks, oids)}."""
    n = params["rows"]
    base_pks = PK_BASE + np.arange(n, dtype=np.int64)
    both, only_theirs = sets["add_add"], sets["theirs_insert"]

    ours = base_oids.copy()
    rewritten = np.concatenate(
        [sets[k] for k in ("edit_edit", "edit_delete", "same", "ours_edit")]
    )
    ours[rewritten] = write(base_pks[rewritten], base_layer.new_rating(base_pks[rewritten]))
    ours_pks = np.concatenate([base_pks, both])
    ours_oids = np.concatenate([ours, write(both, base_layer.old_rating(both))])

    theirs = base_oids.copy()
    rewritten = np.concatenate([sets["edit_edit"], sets["theirs_edit"]])
    theirs[rewritten] = write(base_pks[rewritten], theirs_rating(base_pks[rewritten]))
    theirs[sets["same"]] = ours[sets["same"]]  # the same blob
    keep = np.ones(n, dtype=bool)
    keep[sets["edit_delete"]] = False
    keep[sets["theirs_delete"]] = False
    theirs_pks = np.concatenate([base_pks[keep], both, only_theirs])
    theirs_oids = np.concatenate([
        theirs[keep], write(both, theirs_rating(both)),
        write(only_theirs, base_layer.old_rating(only_theirs)),
    ])
    return {"ours": (ours_pks, ours_oids), THEIRS: (theirs_pks, theirs_oids)}


def expected_merge(params, sets, base_oids, columns):
    """What ``kart merge theirs`` has to answer, from the edit sets alone.
    -> {"conflict_pks" sorted int64 (c,), "conflict_present" bool (3, c),
    "conflict_oids" uint8 (3, c, 20) (zeros where absent) — ancestor, ours,
    theirs —, "merged" (pks, oids): ours with theirs' clean changes,
    "take_theirs": how many keys theirs alone changed}."""
    n = params["rows"]
    base_pks = PK_BASE + np.arange(n, dtype=np.int64)
    ours_pks, ours_oids = columns["ours"]
    theirs_pks, theirs_oids = columns[THEIRS]

    def version(pks, oids, wanted):
        at = np.searchsorted(pks, wanted)
        found = (at < len(pks)) & (pks[np.minimum(at, len(pks) - 1)] == wanted)
        out = np.zeros((len(wanted), 20), dtype=np.uint8)
        out[found] = oids[at[found]]
        return found, out

    conflict_pks = np.sort(np.concatenate([
        base_pks[sets["edit_edit"]], base_pks[sets["edit_delete"]], sets["add_add"],
    ]))
    versions = [
        version(pks, oids, conflict_pks)
        for pks, oids in ((base_pks, base_oids), (ours_pks, ours_oids),
                          (theirs_pks, theirs_oids))
    ]

    merged_oids = ours_oids.copy()
    taken = sets["theirs_edit"]  # ours' first n rows are the base's
    merged_oids[taken] = version(theirs_pks, theirs_oids, base_pks[taken])[1]
    keep = np.ones(len(ours_pks), dtype=bool)
    keep[sets["theirs_delete"]] = False
    added = sets["theirs_insert"]
    merged = (
        np.concatenate([ours_pks[keep], added]),
        np.concatenate([merged_oids[keep], version(theirs_pks, theirs_oids, added)[1]]),
    )
    return {
        "conflict_pks": conflict_pks,
        "conflict_present": np.stack([v[0] for v in versions]),
        "conflict_oids": np.stack([v[1] for v in versions]),
        "merged": merged,
        "take_theirs": len(taken) + len(sets["theirs_delete"]) + len(added),
    }


def _grown_rows(params):
    """Rows of the layer grown by everything either branch appends: where an
    appended point lies (inside the lon/lat range, which ``rows`` would leave)."""
    return params["rows"] + _count(params, "add_add") + _count(params, "theirs_insert")


def _commit(repo, meta, params, ref, message, pks, oids):
    """One branch's commit on ``ref``, its feature tree and sidecar made whole
    from its columns; -> the commit's oid."""
    from kart_tpu.core.feature_tree import build_int_feature_tree
    from kart_tpu.core.objects import MODE_TREE
    from kart_tpu.core.tree_builder import TreeBuilder
    from kart_tpu.diff import sidecar
    from kart_tpu.models.dataset import Dataset3

    odb = repo.odb
    with odb.bulk_pack(level=0):
        ftree = build_int_feature_tree(odb, pks, oids)
        tb = TreeBuilder(odb, meta["root"])
        tb.insert(f"{base_layer.DS_PATH}/{Dataset3.DATASET_DIRNAME}/feature", ftree,
                  mode=MODE_TREE)
        root = tb.flush()
    commit = repo.create_commit(ref, root, message, [meta["commit"]])
    envelopes = None
    if params.get("envelopes"):
        n = params["rows"]
        envelopes = np.concatenate([
            base_layer.envelopes(*base_layer.origins(params["geometry"], part, rows))
            for part, rows in ((pks[pks < PK_BASE + n], n),
                               (pks[pks >= PK_BASE + n], _grown_rows(params)))
        ])
    sidecar.save_sidecar(repo, ftree, pks, oids, envelopes=envelopes)
    return commit


def add_edit_commit(base, work, params, seed):
    """The run's repository: a thin one at ``work/repo`` over the base's
    objects, ours on ``HEAD``'s branch and theirs on ``refs/heads/theirs``,
    each one commit on the import commit.
    -> (repo path, {"repo", "head": ours' commit, "theirs", "ancestor",
    "conflict_pks", "conflict_present", "conflict_oids", "conflicts",
    "take_theirs", "union", "merged_pks", "merged_oids", "n_edits"})."""
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

    sets = edit_sets(params, seed)
    base_oids = np.load(os.path.join(base, "oids.npy"))
    n, grown = params["rows"], _grown_rows(params)
    odb = repo.odb

    def write(pks, ratings):
        # a base point lies where `rows` puts it, an appended one where the
        # layer grown by the appended rows does
        appended = len(pks) and pks[0] >= PK_BASE + n
        return base_layer.write_blobs(
            odb, params["geometry"], pks, ratings, grown if appended else n
        )

    with odb.bulk_pack(level=0):
        columns = branch_columns(params, sets, base_oids, write)
    ours = _commit(repo, meta, params, "HEAD", "ours", *columns["ours"])
    theirs = _commit(
        repo, meta, params, f"refs/heads/{THEIRS}", THEIRS, *columns[THEIRS]
    )
    expected = expected_merge(params, sets, base_oids, columns)
    merged_pks, merged_oids = expected.pop("merged")
    return path, {
        **expected,
        "repo": path,
        "head": ours,
        "theirs": theirs,
        "ancestor": meta["commit"],
        "conflicts": len(expected["conflict_pks"]),
        "union": n + len(sets["add_add"]) + len(sets["theirs_insert"]),
        "merged_pks": merged_pks,
        "merged_oids": merged_oids,
        "n_edits": sum(len(rows) for rows in sets.values()),
    }
