"""3-way merge engine (reference: kart/merge.py + kart/merge_util.py).

The reference delegates tree merging to libgit2 (`repo.merge_trees`,
`kart/merge.py:99-100`) and inherits per-feature conflicts from the
one-feature-one-blob layout. Here the same semantics are computed directly:
feature sets go through the vectorized 3-way rule
(`kart_tpu/ops/merge_kernel.py`, called by `diff/backend.py merge_classify`)
— two diffs of each dataset, ancestor -> ours and ancestor -> theirs, on the
engine `kart diff` would take, and the rule over the keys they changed —
and the small residue (meta items, attachments)
through an identical host-side rule. Clean changes are written to a merged
tree immediately; conflicts become a MergeIndex and move the repo to the
MERGING state, exactly like the reference's state machine
(`kart/repo.py:53-72`).
"""

import json
import logging

import numpy as np

from kart_tpu import telemetry as tm
from kart_tpu.core.repo import (
    MERGE_BRANCH,
    MERGE_HEAD,
    MERGE_INDEX,
    MERGE_MSG,
    InvalidOperation,
    KartRepoState,
)
from kart_tpu.core.structure import RepoStructure
from kart_tpu.core.tree_builder import TreeBuilder
from kart_tpu.merge.index import (
    AncestorOursTheirs,
    ColumnarConflicts,
    CombinedConflicts,
    ConflictEntry,
    EncodedPkPaths,
    MergeIndex,
    PkLabels,
    RowPaths,
)
from kart_tpu.ops.blocks import FeatureBlock, oid_rows_u8, unpack_oid_hex
from kart_tpu.ops.merge_kernel import CONFLICT, KEEP_OURS, TAKE_THEIRS


class MergeResult:
    """Outcome of do_merge."""

    def __init__(
        self,
        *,
        commit_oid=None,
        fast_forward=False,
        already_merged=False,
        merge_index=None,
        dry_run=False,
        stats=None,
        merging=False,
        merged_tree=None,
    ):
        self.commit_oid = commit_oid
        self.fast_forward = fast_forward
        self.already_merged = already_merged
        self.merge_index = merge_index
        self.dry_run = dry_run
        self.stats = stats or {}
        self.merging = merging
        self.merged_tree = merged_tree

    @property
    def has_conflicts(self):
        return self.merge_index is not None and bool(self.merge_index.conflicts)


def _dataset_blocks(repo, structures, ds_path):
    """Per-version FeatureBlock for ds_path (absent dataset -> empty block),
    read as ``kart diff`` reads a revision: the feature tree's KCOL sidecar,
    keys and oids mapped and not copied, the block stamped with its tree's
    oid (so the device classify keeps and finds its pages,
    ops/resident.py). A tree with no sidecar yet is walked once and its
    sidecar saved (:func:`kart_tpu.diff.sidecar.ensure_block`); counter
    ``merge.tree_walk_rows`` says how many rows such walks read."""
    from kart_tpu.diff import sidecar

    blocks = []
    datasets = []
    walked = 0
    with tm.span("merge.load_blocks") as span:
        for structure in structures:
            ds = structure.datasets.get(ds_path) if structure.tree is not None else None
            datasets.append(ds)
            block = None
            if ds is not None and ds.feature_tree is not None:
                walks = not sidecar.has_sidecar(repo, ds)
                block = sidecar.ensure_block(repo, ds, pad=False)
                if block is None:
                    # no sidecar could be written or read back
                    block = FeatureBlock.from_dataset(ds)
                if walks:
                    walked += block.count
            if block is None:
                block = FeatureBlock.from_arrays(
                    np.zeros(0, dtype=np.int64), np.zeros((0, 5), np.uint32), []
                )
            blocks.append(block)
        span.set(
            rows_ancestor=blocks[0].count,
            rows_ours=blocks[1].count,
            rows_theirs=blocks[2].count,
            source="tree_walk" if walked else "sidecar",
        )
    tm.incr("merge.tree_walk_rows", walked)
    return blocks, datasets


def _keys_to_block_rows(block, keys):
    """union keys (K,) -> row index into block for each key, or -1 when the
    key is absent. One batched searchsorted, no per-key Python."""
    real = block.keys[: block.count]
    idx = np.searchsorted(real, keys)
    idxc = np.minimum(idx, max(block.count - 1, 0))
    found = (
        (real[idxc] == keys) & (idx < block.count)
        if block.count
        else np.zeros(len(keys), dtype=bool)
    )
    return np.where(found, idxc, -1)


def _feature_label(ds_path, datasets, rel_paths):
    """Conflict label `<ds>:feature:<pk>` (reference RichConflict labels,
    kart/merge_util.py:508-540)."""
    for ds, rel in zip(datasets, rel_paths):
        if ds is not None and rel is not None:
            try:
                pks = ds.decode_path_to_pks(rel)
                pk_part = ",".join(str(pk) for pk in pks)
                return f"{ds_path}:feature:{pk_part}"
            except Exception:
                continue
    rel = next((r for r in rel_paths if r), "?")
    return f"{ds_path}:feature:{rel}"


#: An int-pk layer's merged feature tree is made whole from the merged
#: (pk, oid) columns where theirs' clean changes number at least one in this
#: many of ours' rows; below it they go into the tree builder path by path.
#: Whole, the tree costs a fixed time a row of the layer whatever changed
#: (names, tree order, one hash a leaf: the native leaf stream of
#: core/feature_tree.py) and is written as one pack, or not at all where the
#: store holds it; by path every touched leaf is parsed and written again
#: as a loose object at the flush. Measured at 4M rows with the native leaf
#: stream, on two hosts (PERF.md section 5, PR 40): the two cross at one row
#: in 900-1,100 where a loose object costs 0.3 ms to write, and at one in
#: 1,800 (objects there) to above one in 4,050 (objects written) where it
#: costs 3 ms, as on the chip's host. Set at the share measured on both
#: where they disagree least: the whole tree costs the first host 0.5-0.7 s
#: too much there and saves the second 2.1 s. The by-path side has no cell
#: in the benchmark (PERF.md section 7).
REBUILD_MIN_SHARE = 4096


def _int_encoder(ds):
    encoder = getattr(ds, "path_encoder", None)
    return encoder if getattr(encoder, "scheme", None) == "int" else None


def _feature_paths(prefix, block, ds, rows):
    """Repository paths of ``rows`` of one version, as one column: encoded in
    a batch from the pks where the path is a function of the pk."""
    encoder = _int_encoder(ds)
    if encoder is not None:
        return EncodedPkPaths(prefix, encoder, block.keys[rows]).batch()
    return RowPaths(prefix, block.paths, rows).batch()


def _merged_batches(o_block, t_block, rewritten, rewriting, removed, added, branches):
    """The merged (pk, oid) columns of an int-pk layer in key order, a batch
    of ours' rows at a time: -> (pks int64, oids uint8 (n, 20)) per batch.
    ``rewritten`` are ours' rows theirs rewrote and ``rewriting`` theirs'
    rows that did, ``removed`` ours' rows theirs deleted, ``added`` theirs'
    rows ours lacks — all ascending. A batch at a time, so that nothing of
    the layer's size is copied whole (core/feature_tree.py
    ``LEAF_STREAM_ROWS`` says why that matters), and every batch cut where a
    leaf of the feature tree ends (``branches`` keys a leaf): all keys below
    the first key of the leaf ours' next row lies in, so that the leaf
    stream can hand each batch to another thread as it comes."""
    from kart_tpu.core.feature_tree import LEAF_STREAM_ROWS

    n = o_block.count
    o_keys = o_block.keys[:n]
    new_oids = oid_rows_u8(t_block.oids[rewriting])
    added_keys, added_oids = t_block.keys[added], oid_rows_u8(t_block.oids[added])
    taken = 0  # of the added keys
    lo = 0
    while lo < n:
        hi = min(lo + LEAF_STREAM_ROWS, n)
        if hi < n:
            bound = o_keys[hi] // branches * branches  # that leaf's first key
            cut = lo + int(np.searchsorted(o_keys[lo:hi], bound))
            if cut > lo:
                hi = cut
            else:  # a leaf longer than a batch
                bound = o_keys[hi]
        pks, oids = o_keys[lo:hi], oid_rows_u8(o_block.oids[lo:hi])  # oids: a copy
        first, last = np.searchsorted(rewritten, (lo, hi))
        oids[rewritten[first:last] - lo] = new_oids[first:last]
        first, last = np.searchsorted(removed, (lo, hi))
        if last > first:
            keep = np.ones(hi - lo, dtype=bool)
            keep[removed[first:last] - lo] = False
            pks, oids = pks[keep], oids[keep]
        # theirs' new keys that belong before ours' next batch
        upto = len(added_keys) if hi == n else int(np.searchsorted(added_keys, bound))
        if upto > taken:
            at = np.searchsorted(pks, added_keys[taken:upto])
            pks = np.insert(pks, at, added_keys[taken:upto])
            oids = np.insert(oids, at, added_oids[taken:upto], axis=0)
            taken = upto
        if len(pks):
            yield pks, oids
        lo = hi
    if taken < len(added_keys):  # ours is empty
        yield added_keys[taken:], added_oids[taken:]


def _apply_take_theirs(inner, blocks, datasets, take_keys, tree_builder):
    """Theirs' clean changes, applied to ours' tree in ``tree_builder``:
    blobs theirs wrote are inserted, paths theirs deleted are removed."""
    _, o_block, t_block = blocks
    _, o_ds, t_ds = datasets
    prefix = f"{inner}/feature/"
    with tm.span("merge.apply", take_theirs=len(take_keys)) as span:
        t_rows = _keys_to_block_rows(t_block, take_keys)
        o_rows = _keys_to_block_rows(o_block, take_keys)
        present = t_rows >= 0
        inserted = t_rows[present]
        removed = o_rows[~present & (o_rows >= 0)]
        span.set(inserted=len(inserted), removed=len(removed), trees_written=0)
        encoder = _int_encoder(o_ds)
        rewrites = o_rows[present] >= 0  # of what theirs wrote: ours has the key
        if (
            encoder is not None
            and (len(inserted) == 0 or _int_encoder(t_ds) == encoder)
            and len(take_keys) * REBUILD_MIN_SHARE >= o_block.count
            and o_block.count - len(removed) + int(np.count_nonzero(~rewrites)) > 0
        ):
            # the merged columns: ours' rows, theirs' oid where theirs
            # rewrote one, less what theirs deleted, with what theirs added
            from kart_tpu.core.feature_tree import write_int_feature_tree
            from kart_tpu.core.objects import MODE_TREE

            feature_tree = write_int_feature_tree(
                tree_builder.odb,
                lambda: _merged_batches(
                    o_block, t_block, o_rows[present][rewrites],
                    inserted[rewrites], removed, inserted[~rewrites],
                    encoder.branches,
                ),
                encoder,
            )
            tree_builder.insert(f"{inner}/feature", feature_tree, mode=MODE_TREE)
            span.set(trees_written=1)
            return
        if len(inserted):
            tree_builder.insert_many(
                _feature_paths(prefix, t_block, t_ds, inserted),
                unpack_oid_hex(t_block.oids[inserted]),
            )
        for path in _feature_paths(prefix, o_block, o_ds, removed):
            tree_builder.remove(path)


def _merge_dataset_features(repo, ds_path, structures, tree_builder):
    """Vectorized per-feature 3-way for one dataset. Mutates tree_builder with
    clean theirs-changes; -> (conflicts dict, stats)."""
    blocks, datasets = _dataset_blocks(repo, structures, ds_path)

    if any(b.has_key_collisions() for b in blocks):
        # hash-keyed identity collided (~1e-4 probability at 1e8 features):
        # host path with identical semantics
        return _merge_dataset_features_host(ds_path, blocks, datasets, tree_builder)

    from kart_tpu.diff.backend import merge_classify

    union, decision, presence, stats = merge_classify(*blocks)
    tm.incr("merge.conflicts", stats["conflicts"])
    tm.incr("merge.take_theirs", stats["take_theirs"])

    inner = None
    for ds in datasets:
        if ds is not None:
            inner = ds.inner_path
            break
    if inner is None:
        return {}, stats

    take_idx = np.nonzero(decision == TAKE_THEIRS)[0]
    if len(take_idx):
        _apply_take_theirs(inner, blocks, datasets, union[take_idx], tree_builder)

    conflict_idx = np.nonzero(decision == CONFLICT)[0]
    with tm.span("merge.conflicts", conflicts=len(conflict_idx)):
        conflicts = materialise_conflicts(
            ds_path, blocks, datasets, inner, union, conflict_idx
        )
    return conflicts, stats


def materialise_conflicts(ds_path, blocks, datasets, inner, union, conflict_idx):
    """Conflict rows -> ColumnarConflicts (a {label: AncestorOursTheirs}
    mapping stored as numpy columns). BASELINE config #5 scale: a
    1M-conflict merge builds three (present, oids) column pairs with one
    searchsorted + one gather each — labels, paths and entry objects stay
    lazy until something actually reads them (serialisation reads the
    columns in batch)."""
    if not len(conflict_idx):
        return {}
    conflict_keys = union[conflict_idx]
    n = len(conflict_keys)
    prefix = f"{inner}/feature/"

    versions = []
    rows_per_block = []
    pk_path_cols = {}  # encoder id -> shared EncodedPkPaths (encode once)
    for block, ds in zip(blocks, datasets):
        rows = _keys_to_block_rows(block, conflict_keys)
        present = rows >= 0
        rows_per_block.append(rows)
        oids_u8 = np.zeros((n, 20), dtype=np.uint8)
        if np.any(present):
            sel = np.ascontiguousarray(block.oids[rows[present]])
            oids_u8[present] = sel.view(np.uint8).reshape(-1, 20)
        encoder = getattr(ds, "path_encoder", None)
        if encoder is not None and getattr(encoder, "scheme", None) == "int":
            # int-pk: the path is a pure function of the pk — versions with
            # the same encoder share one lazy column
            paths = pk_path_cols.get(id(encoder))
            if paths is None:
                paths = EncodedPkPaths(prefix, encoder, conflict_keys)
                pk_path_cols[id(encoder)] = paths
        else:
            paths = RowPaths(prefix, block.paths, rows)
        versions.append((present, oids_u8, paths))

    schemes = {
        getattr(getattr(ds, "path_encoder", None), "scheme", None)
        for ds in datasets
        if ds is not None
    }
    if schemes == {"int"}:
        # every version int-pk: keys ARE the pks, labels derive from the key
        # column. Mixed-encoder datasets (pk type change) must decode each
        # conflict with the encoder of a version that actually holds it.
        labels = PkLabels(ds_path, conflict_keys)
    else:
        labels = _DeferredLabels(ds_path, datasets, blocks, rows_per_block, n)
    return ColumnarConflicts(labels, versions)


class _DeferredLabels:
    """Label column for hash-keyed datasets: path-decode runs only when the
    labels are first read (serialisation / conflict listing)."""

    __slots__ = ("ds_path", "datasets", "blocks", "rows_per_block", "n")

    def __init__(self, ds_path, datasets, blocks, rows_per_block, n):
        self.ds_path = ds_path
        self.datasets = datasets
        self.blocks = blocks
        self.rows_per_block = rows_per_block
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.batch()[i]  # single lookups are rare; batch is cached upstream

    def batch(self):
        per_block = [
            (rows.tolist(), (rows >= 0).tolist(), None)
            for rows in self.rows_per_block
        ]
        return _conflict_labels_batch(
            self.ds_path, self.datasets, self.blocks, per_block, self.n
        )


def _conflict_labels_batch(ds_path, datasets, blocks, per_block, n):
    """Labels `<ds>:feature:<pk>` for every conflict. Each conflict's rel
    path is decoded with the encoder of the version it came from (versions
    of one dataset can carry different encoders, e.g. after a pk type
    change); int-pk versions decode all their paths in one vectorized
    call, others fall back per-path."""
    labels = [None] * n
    for v, ((rows, found, _), block) in enumerate(zip(per_block, blocks)):
        pending = [i for i in range(n) if labels[i] is None and found[i]]
        if not pending:
            continue
        rels = [block.paths[rows[i]] for i in pending]
        ds = datasets[v]
        encoder = getattr(ds, "path_encoder", None)
        done = False
        if encoder is not None and hasattr(encoder, "decode_paths_batch"):
            try:
                pks = encoder.decode_paths_batch(rels)
                for i, pk in zip(pending, pks):
                    labels[i] = f"{ds_path}:feature:{pk}"
                done = True
            except Exception as e:
                # undecodable batch: the per-path loop below re-derives
                # every label individually
                logging.getLogger(__name__).debug(
                    "batch path decode failed for %s: %s", ds_path, e
                )
        if not done:
            version_datasets = [None] * len(blocks)
            version_datasets[v] = ds
            for i, rel in zip(pending, rels):
                rel_row = [None] * len(blocks)
                rel_row[v] = rel
                labels[i] = _feature_label(ds_path, version_datasets, rel_row)
    for i in range(n):
        if labels[i] is None:
            labels[i] = f"{ds_path}:feature:?"
    return labels


def _merge_dataset_features_host(ds_path, blocks, datasets, tree_builder):
    """Fallback with dict semantics when hash keys collide."""
    def index(block):
        hexes = unpack_oid_hex(block.oids[: block.count])
        return dict(zip(block.paths, hexes))

    a, o, t = (index(b) for b in blocks)
    inner = next((ds.inner_path for ds in datasets if ds is not None), None)
    conflicts = {}
    stats = {"conflicts": 0, "take_theirs": 0}
    for rel in sorted(set(a) | set(o) | set(t)):
        av, ov, tv = a.get(rel), o.get(rel), t.get(rel)
        if ov == tv or tv == av:
            continue
        if ov == av:
            stats["take_theirs"] += 1
            if tv is not None:
                tree_builder.insert(f"{inner}/feature/{rel}", tv)
            else:
                tree_builder.remove(f"{inner}/feature/{rel}")
        else:
            stats["conflicts"] += 1
            label = _feature_label(ds_path, datasets, [rel] * 3)
            conflicts[label] = AncestorOursTheirs(
                *(
                    ConflictEntry(f"{inner}/feature/{rel}", v) if v is not None else None
                    for v in (av, ov, tv)
                )
            )
    return conflicts, stats


def _non_feature_items(structure):
    """{repo_path: oid} for every blob that is not a feature blob (meta items,
    version blob, attachments). Walks only the dataset inner trees' non-feature
    subtrees plus everything outside dataset trees — never descends into
    feature/ (which holds the ~all of the repo's blobs)."""
    out = {}
    tree = structure.tree
    if tree is None:
        return out

    dataset_dirnames = {".table-dataset", ".sno-dataset"}

    def walk(node, prefix):
        for entry in node.entries():
            path = f"{prefix}{entry.name}"
            if not entry.is_tree:
                out[path] = entry.oid
                continue
            if entry.name in dataset_dirnames:
                inner = structure.repo.odb.tree(entry.oid)
                for inner_entry in inner.entries():
                    if inner_entry.name == "feature":
                        continue
                    if inner_entry.is_tree:
                        walk(
                            structure.repo.odb.tree(inner_entry.oid),
                            f"{path}/{inner_entry.name}/",
                        )
                    else:
                        out[f"{path}/{inner_entry.name}"] = inner_entry.oid
            else:
                walk(structure.repo.odb.tree(entry.oid), f"{path}/")

    walk(tree, "")
    return out


def _label_for_non_feature(structure_list, path):
    for structure in structure_list:
        if structure.tree is None:
            continue
        ds_path, part, item = structure.decode_path(path)
        if part == "meta":
            return f"{ds_path}:meta:{item}"
        break
    return f"<root>:attachment:{path}"


def _merge_non_features(structures, tree_builder):
    a_items, o_items, t_items = (_non_feature_items(s) for s in structures)
    conflicts = {}
    for path in sorted(set(a_items) | set(o_items) | set(t_items)):
        av, ov, tv = a_items.get(path), o_items.get(path), t_items.get(path)
        if ov == tv or tv == av:
            continue
        if ov == av:
            if tv is not None:
                tree_builder.insert(path, tv)
            else:
                tree_builder.remove(path)
        else:
            label = _label_for_non_feature(structures, path)
            conflicts[label] = AncestorOursTheirs(
                *(
                    ConflictEntry(path, v) if v is not None else None
                    for v in (av, ov, tv)
                )
            )
    return conflicts


def merge_trees_vectorized(repo, ancestor_struct, ours_struct, theirs_struct):
    """-> (merged_tree_oid, conflicts dict, stats). The merged tree contains
    every clean change; conflicted paths keep their `ours` content until
    resolved."""
    structures = (ancestor_struct, ours_struct, theirs_struct)
    tb = TreeBuilder(repo.odb, ours_struct.tree_oid)
    all_conflicts = CombinedConflicts()
    total_stats = {"take_theirs": 0, "conflicts": 0}

    ds_paths = set()
    for structure in structures:
        if structure.tree is not None:
            ds_paths.update(structure.datasets.paths())
    for ds_path in sorted(ds_paths):
        conflicts, stats = _merge_dataset_features(repo, ds_path, structures, tb)
        all_conflicts.add(conflicts)
        for k in total_stats:
            total_stats[k] += stats.get(k, 0)

    with tm.span("merge.non_features"):
        all_conflicts.add(_merge_non_features(structures, tb))
    with tm.span("merge.write_tree", changes=tb.change_count):
        merged_tree = tb.flush() if tb else ours_struct.tree_oid
    return merged_tree, all_conflicts, total_stats


def do_merge(repo, theirs_refish, *, message=None, dry_run=False, ff=True, ff_only=False):
    """Merge `theirs_refish` into HEAD (reference: kart/merge.py:45-158)."""
    if repo.state != KartRepoState.NORMAL:
        raise InvalidOperation(
            KartRepoState.bad_state_message(repo.state, (KartRepoState.NORMAL,))
        )
    ours_oid = repo.head_commit_oid
    if ours_oid is None:
        raise InvalidOperation("Repository has no commits yet")
    theirs_oid, theirs_ref = _resolve_commit_and_ref(repo, theirs_refish)
    if theirs_oid is None:
        raise InvalidOperation(f"Cannot resolve {theirs_refish!r}")

    ancestor_oid = repo.merge_base(ours_oid, theirs_oid)
    if ancestor_oid is None:
        raise InvalidOperation("Commits have no common ancestor")

    if ancestor_oid == theirs_oid:
        return MergeResult(already_merged=True, commit_oid=ours_oid, dry_run=dry_run)
    if ancestor_oid == ours_oid and ff:
        # fast-forward
        if not dry_run:
            _update_head_to(repo, theirs_oid)
        return MergeResult(commit_oid=theirs_oid, fast_forward=True, dry_run=dry_run)
    if ff_only:
        raise InvalidOperation(
            "Can't resolve as a fast-forward merge and --ff-only specified"
        )

    ancestor_struct = RepoStructure(repo, ancestor_oid)
    ours_struct = RepoStructure(repo, ours_oid)
    theirs_struct = RepoStructure(repo, theirs_oid)

    merged_tree, conflicts, stats = merge_trees_vectorized(
        repo, ancestor_struct, ours_struct, theirs_struct
    )

    branch_name = _branch_shorthand(repo, theirs_refish, theirs_ref)
    if message is None:
        message = f'Merge branch "{branch_name}"' if branch_name else (
            f"Merge {theirs_oid[:8]}"
        )

    if conflicts:
        merge_index = MergeIndex(merged_tree, conflicts)
        if not dry_run:
            merge_index.write_to_repo(repo)
            repo.write_gitdir_file(MERGE_HEAD, theirs_oid)
            repo.write_gitdir_file(MERGE_MSG, message)
            if branch_name:
                repo.write_gitdir_file(MERGE_BRANCH, branch_name)
        return MergeResult(
            merge_index=merge_index,
            dry_run=dry_run,
            stats=stats,
            merging=not dry_run,
            merged_tree=merged_tree,
        )

    if dry_run:
        return MergeResult(dry_run=True, stats=stats, merged_tree=merged_tree)

    commit_oid = _create_merge_commit(repo, merged_tree, message, [ours_oid, theirs_oid])
    _reset_wc(repo)
    return MergeResult(commit_oid=commit_oid, stats=stats, merged_tree=merged_tree)


def complete_merging_state(repo, *, message=None):
    """`kart merge --continue` (reference: kart/merge.py:183-236)."""
    if repo.state != KartRepoState.MERGING:
        raise InvalidOperation("No merge is ongoing")
    merge_index = MergeIndex.read_from_repo(repo)
    unresolved = merge_index.unresolved_labels
    if unresolved:
        raise InvalidOperation(
            f"Merge is not yet complete - {len(unresolved)} conflicts "
            'still need resolving. See "kart conflicts" / "kart resolve"'
        )
    theirs_oid = repo.read_gitdir_file(MERGE_HEAD).strip()
    message = message or repo.read_gitdir_file(MERGE_MSG) or "Merge"
    final_tree = merge_index.write_resolved_tree(repo.odb)
    commit_oid = _create_merge_commit(
        repo, final_tree, message, [repo.head_commit_oid, theirs_oid]
    )
    abort_merging_state(repo)
    _reset_wc(repo)
    return commit_oid


def abort_merging_state(repo):
    """Delete MERGE_* state files (reference: kart/merge.py:161-180).
    Robust: removes whatever subset exists."""
    for name in (MERGE_HEAD, MERGE_INDEX, MERGE_BRANCH, MERGE_MSG):
        repo.remove_gitdir_file(name)


def _resolve_commit_and_ref(repo, refish):
    oid, ref = repo.resolve_refish(refish)
    if oid is not None:
        oid = repo._peel_to_commit_oid(oid)
    return oid, ref


def _branch_shorthand(repo, refish, ref):
    if ref and ref.startswith("refs/heads/"):
        return ref[len("refs/heads/") :]
    if ref and ref.startswith("refs/remotes/"):
        return ref[len("refs/remotes/") :]
    if isinstance(refish, str) and not all(
        c in "0123456789abcdef" for c in refish.lower()
    ):
        return refish
    return None


def _update_head_to(repo, commit_oid):
    branch = repo.head_branch
    if branch:
        repo.refs.set(branch, commit_oid, log_message="merge: fast-forward")
    else:
        repo.refs.set_head(commit_oid, log_message="merge: fast-forward")
    _reset_wc(repo)


def _create_merge_commit(repo, tree_oid, message, parents):
    ref = repo.head_branch or "HEAD"
    return repo.create_commit(ref, tree_oid, message, parents)


def _reset_wc(repo):
    from kart_tpu.workingcopy import get_working_copy

    wc = get_working_copy(repo)
    if wc is not None:
        wc.reset(RepoStructure(repo, "HEAD"), force=True)
