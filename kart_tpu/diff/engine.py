"""Diff orchestration (reference: kart/diff_util.py, rich_base_dataset.py:170-300).

Two layers:

* **Tree diff** (host): prune-walk two feature trees, skipping identical
  subtree oids — O(changed), independent of dataset size. Produces the
  changed (path, old_oid, new_oid) set.
* **Classification + values** (vectorized / lazy): changed paths become lazy
  Deltas; bulk classification of whole datasets (for working-copy compare,
  merge, estimation) runs as sorted (pk, oid) array joins — see
  kart_tpu/ops/diff_kernel.py for the device kernels.
"""

import threading
from collections import namedtuple

import numpy as np

from kart_tpu import telemetry as tm
from kart_tpu.core.odb import TreeView
from kart_tpu.diff.key_filters import RepoKeyFilter
from kart_tpu.diff.structs import (
    DatasetDiff,
    Delta,
    DeltaDiff,
    KeyValue,
    RepoDiff,
)


def _native_tree_diff_rows(odb, tree_oid_a, tree_oid_b):
    """Differing entries of two tree objects via the C merge-walk, or None
    (lib unavailable / non-tree object) — the Python path below parses
    every entry of both trees into objects when ~99% are equal at 1%-edit
    scale (measured ~6s of a 1M-row tree-engine diff)."""
    from kart_tpu import native

    try:
        type_a, content_a = odb.read_raw(tree_oid_a)
        type_b, content_b = odb.read_raw(tree_oid_b)
    except Exception:
        return None
    if type_a != "tree" or type_b != "tree":
        return None
    return native.tree_diff_raw(content_a, content_b)


def tree_diff_entries(odb, tree_oid_a, tree_oid_b, prefix=""):
    """Yield (path, old_entry_oid, new_entry_oid) for each *blob* that differs
    between two trees (either side may be None). Subtrees with equal oids are
    skipped wholesale — the git tree-diff contract the whole design leans on."""
    if tree_oid_a == tree_oid_b:
        return
    if tree_oid_a is not None and tree_oid_b is not None:
        rows = _native_tree_diff_rows(odb, tree_oid_a, tree_oid_b)
        if rows is not None:
            for name, oid_a, oid_b, a_is_tree, b_is_tree in sorted(
                rows, key=lambda r: r[0]
            ):
                path = f"{prefix}{name}"
                if a_is_tree or b_is_tree:
                    yield from tree_diff_entries(
                        odb,
                        oid_a if a_is_tree else None,
                        oid_b if b_is_tree else None,
                        path + "/",
                    )
                    if oid_a is not None and not a_is_tree:
                        yield path, oid_a, None
                    if oid_b is not None and not b_is_tree:
                        yield path, None, oid_b
                else:
                    yield path, oid_a, oid_b
            return
    entries_a = {e.name: e for e in odb.read_tree_entries(tree_oid_a)} if tree_oid_a else {}
    entries_b = {e.name: e for e in odb.read_tree_entries(tree_oid_b)} if tree_oid_b else {}
    for name in sorted(entries_a.keys() | entries_b.keys()):
        ea, eb = entries_a.get(name), entries_b.get(name)
        oid_a = ea.oid if ea else None
        oid_b = eb.oid if eb else None
        if oid_a == oid_b:
            continue
        a_is_tree = ea.is_tree if ea else False
        b_is_tree = eb.is_tree if eb else False
        path = f"{prefix}{name}"
        if a_is_tree or b_is_tree:
            yield from tree_diff_entries(
                odb,
                oid_a if a_is_tree else None,
                oid_b if b_is_tree else None,
                path + "/",
            )
            # a blob replaced by a tree (or vice versa) also yields the blob side
            if ea and not a_is_tree:
                yield path, oid_a, None
            if eb and not b_is_tree:
                yield path, None, oid_b
        else:
            yield path, oid_a, oid_b


def get_feature_diff(base_ds, target_ds, ds_filter=None):
    """DeltaDiff of features between two versions of a dataset. Lazy values
    (reference: rich_base_dataset.py:205-300)."""
    feature_filter = ds_filter["feature"] if ds_filter is not None else None
    result = DeltaDiff()

    base_tree = base_ds.feature_tree if base_ds else None
    target_tree = target_ds.feature_tree if target_ds else None
    base_oid = base_tree.oid if base_tree is not None else None
    target_oid = target_tree.oid if target_tree is not None else None
    if base_oid == target_oid:
        return result

    odb = (base_tree or target_tree).odb
    # the span covers walk + (lazy) delta construction: the walk stays a
    # streamed generator — buffering it just to time it would add an
    # O(changed) transient at exactly the scale this engine serves
    with tm.span("diff.tree_walk"):
        for path, old_oid, new_oid in tree_diff_entries(odb, base_oid, target_oid):
            ds = base_ds if old_oid is not None else target_ds
            pks = ds.decode_path_to_pks(path)
            key = pks[0] if len(pks) == 1 else pks
            if feature_filter is not None and key not in feature_filter:
                continue
            # values resolve by the oid the tree diff already produced — no
            # second path->tree walk at materialisation time
            old = (
                KeyValue((key, base_ds.get_feature_promise_from_oid(pks, old_oid)))
                if old_oid is not None
                else None
            )
            new = (
                KeyValue((key, target_ds.get_feature_promise_from_oid(pks, new_oid)))
                if new_oid is not None
                else None
            )
            result.add_delta(Delta(old, new))
    return result


def _pks_for_index(block, ds, i):
    """pk tuple for a block row — direct from the key for int-pk blocks
    (sidecar blocks recompute paths from pks, so going via the path would
    round-trip for nothing), via path decode otherwise."""
    from kart_tpu.diff.sidecar import IntKeyPaths

    if block.paths is None or isinstance(block.paths, IntKeyPaths):
        # int-pk block (spatially-prefiltered subsets drop the path view
        # entirely — int datasets recompute paths from pks)
        return (int(block.keys[i]),)
    return ds.decode_path_to_pks(block.path_for_index(i))


def _hash_keyed(ds):
    """Is ``ds`` keyed by the hash of its feature filenames (not an int pk)?"""
    encoder = getattr(ds, "path_encoder", None)
    return encoder is not None and encoder.scheme != "int"


def key_guard(old_block, new_block):
    """Why two hash-keyed blocks cannot be joined by key, or None: two rows
    of one side share a key (``within``, as the sidecar recorded it when it
    was written), or a real key is the padding key the kernels take for no
    row (``pad_key``). Counted under ``diff.hash_guard.fallbacks{why}``."""
    for block in (old_block, new_block):
        why = (
            "within" if block.has_key_collisions()
            else "pad_key" if block.has_pad_key() else None
        )
        if why is not None:
            tm.incr("diff.hash_guard.fallbacks", why=why)
            return why
    return None


def _path_rows(paths, rows):
    """The feature paths of block rows ``rows``, as ByteRows."""
    from kart_tpu.models.paths import ByteRows

    if isinstance(paths, ByteRows):
        return paths.take(rows)
    return ByteRows.from_list([paths[int(i)].encode("utf8") for i in rows])


def _rows_differ(a, b):
    """bool per row: do ByteRows ``a`` and ``b`` hold other bytes?"""
    differ = a.lengths() != b.lengths()
    same = np.flatnonzero(~differ)
    if len(same):
        a, b = a.take(same), b.take(same)
        neq = np.asarray(a.data) != np.asarray(b.data)
        if neq.any():
            differ[same] = np.logical_or.reduceat(neq, a.offs[:-1])
    return differ


#: the collision guard's answer for one pair of revisions: UPDATE pairs
#: compared, and how many of them name two paths (or by how many rows the
#: sides' UPDATE counts differ)
GuardVerdict = namedtuple("GuardVerdict", "pairs collisions")


def guard_verdict(where, compute):
    """``compute()`` -> (pairs, collisions) under the span ``diff.hash_guard``
    (``where`` = ``host`` | ``device``: on the device route the span is the
    wait for the device's verdict and its read), counted under
    ``diff.hash_guard.route{where}`` -> GuardVerdict."""
    with tm.span("diff.hash_guard", where=where) as sp:
        pairs, collisions = compute()
        sp.set(pairs=pairs, collisions=collisions)
    tm.incr("diff.hash_guard.route", where=where)
    return GuardVerdict(pairs, collisions)


def host_guard(old_block, new_block, old_class, new_class):
    """The cross-version collision guard of a hash-keyed join, on the host:
    a deleted feature and an inserted one can share a 63-bit key, which the
    join reads as an update. Both sides are sorted by key and keys are
    unique on each, so the k-th UPDATE row of one side pairs with the k-th
    of the other; every pair must name one feature path (the path is a
    function of the filename, so this is the filename compared) — gathered
    from both sides' path columns and compared as one array. -> GuardVerdict
    (:func:`guard_verdict`, ``where=host``)."""
    from kart_tpu.ops.diff_kernel import UPDATE

    def compute():
        old_upd = np.flatnonzero(old_class == UPDATE)
        new_upd = np.flatnonzero(new_class == UPDATE)
        if len(old_upd) != len(new_upd):
            collisions = abs(len(old_upd) - len(new_upd))
        else:
            collisions = int(np.count_nonzero(_rows_differ(
                _path_rows(old_block.paths, old_upd),
                _path_rows(new_block.paths, new_upd),
            )))
        return min(len(old_upd), len(new_upd)), collisions

    return guard_verdict("host", compute)


def guard_passed(verdict):
    """Did every UPDATE pair name one path? Else the fallback is counted
    (``diff.hash_guard.fallbacks{why=across}``) and the caller takes the
    exact path."""
    if verdict.collisions:
        tm.incr("diff.hash_guard.fallbacks", why="across")
    return not verdict.collisions


def hash_guard(old_block, new_block, old_class, new_class):
    """:func:`host_guard` -> True when every UPDATE pair names one path;
    else the fallback is counted and the caller takes the exact path."""
    return guard_passed(host_guard(old_block, new_block, old_class, new_class))


def get_feature_diff_columnar(base_ds, target_ds, ds_filter=None, *, blocks=None):
    """Bulk columnar variant of get_feature_diff: both versions' (pk, oid)
    arrays are classified in one jitted device join, and only changed rows
    get (lazy) Deltas. Semantically identical to the tree-diff path; chosen
    when both sides have sidecar indexes (O(1) mmap loads) or are
    materialised anyway (working-copy compare, merge, benchmarks).
    ``blocks``: optional pre-loaded (old_block, new_block)."""
    from kart_tpu.ops.blocks import FeatureBlock
    from kart_tpu.ops.diff_kernel import (
        DELETE,
        INSERT,
        UPDATE,
        changed_indices,
        classify_blocks,
    )

    def empty_block():
        return FeatureBlock.from_arrays(
            np.zeros(0, dtype=np.int64), np.zeros((0, 5), dtype=np.uint32), []
        )

    feature_filter = ds_filter["feature"] if ds_filter is not None else None
    result = DeltaDiff()
    if blocks is not None:
        old_block, new_block = blocks
    else:
        old_block = FeatureBlock.from_dataset(base_ds) if base_ds is not None else None
        new_block = FeatureBlock.from_dataset(target_ds) if target_ds is not None else None
    old_block = old_block if old_block is not None else empty_block()
    new_block = new_block if new_block is not None else empty_block()
    hash_keyed = _hash_keyed(base_ds or target_ds)
    if (
        key_guard(old_block, new_block) if hash_keyed
        else old_block.has_key_collisions() or new_block.has_key_collisions()
    ):
        # 63-bit hash identity collided (hash-encoded dataset), or a key
        # the kernels take for padding: the exact tree-diff path
        return get_feature_diff(base_ds, target_ds, ds_filter)

    from kart_tpu.diff.backend import classify_span, select_backend

    backend = select_backend(max(old_block.count, new_block.count))
    with classify_span(backend, old_block, new_block):
        old_class, new_class, _ = backend.classify(old_block, new_block)
        old_idx, new_idx = changed_indices(old_class, new_class)
    if hash_keyed and not hash_guard(old_block, new_block, old_class, new_class):
        return get_feature_diff(base_ds, target_ds, ds_filter)

    # values resolve by oid straight from the sidecar columns — no
    # per-feature path->tree walk at materialisation time (measured ~500us
    # per feature at 10M-polygon scale, dominated by uncached parse_tree).
    # Oid hexes are unpacked for all changed rows in two vectorized passes
    # instead of one 5-word view per row.
    from kart_tpu.ops.blocks import unpack_oid_hex

    old_hex = dict(zip((int(i) for i in old_idx), unpack_oid_hex(old_block.oids[old_idx]))) if len(old_idx) else {}
    new_hex = dict(zip((int(i) for i in new_idx), unpack_oid_hex(new_block.oids[new_idx]))) if len(new_idx) else {}
    new_row_by_key = {int(new_block.keys[i]): int(i) for i in new_idx}

    for i in old_idx:
        i = int(i)
        pks = _pks_for_index(old_block, base_ds, i)
        key = pks[0] if len(pks) == 1 else pks
        if feature_filter is not None and key not in feature_filter:
            continue
        cls = old_class[i]
        old_kv = KeyValue(
            (key, base_ds.get_feature_promise_from_oid(pks, old_hex[i]))
        )
        if cls == DELETE:
            result.add_delta(Delta.delete(old_kv))
        else:  # UPDATE — new side added below keyed identically
            j = new_row_by_key.get(int(old_block.keys[i]))
            new_kv = KeyValue(
                (
                    key,
                    target_ds.get_feature_promise_from_oid(pks, new_hex[j])
                    if j is not None
                    else target_ds.get_feature_promise(pks),
                )
            )
            result.add_delta(Delta.update(old_kv, new_kv))
    for i in new_idx:
        i = int(i)
        if new_class[i] != INSERT:
            continue  # updates already added
        pks = _pks_for_index(new_block, target_ds, i)
        key = pks[0] if len(pks) == 1 else pks
        if feature_filter is not None and key not in feature_filter:
            continue
        result.add_delta(
            Delta.insert(
                KeyValue(
                    (key, target_ds.get_feature_promise_from_oid(pks, new_hex[i]))
                )
            )
        )
    return result


def _envelope_hits(block, query):
    """bool (count,) envelope-vs-query intersections for one sidecar block,
    routed through the selected diff backend: host blocks take the
    block-pruned native scan (all-out blocks' envelope pages are never
    read; KART_BLOCK_PRUNE=0 forces the full scan), big blocks on a live
    mesh take the shard_map f32 scan — results are bit-identical on every
    route (fuzz-tested; the device kernel mirrors the native thresholds
    exactly)."""
    from kart_tpu.diff.backend import select_backend

    if block.count == 0:
        return np.zeros(0, dtype=bool)
    return select_backend(block.count).envelope_hits(block, query)


def _run_count(idx):
    """Runs of consecutive row numbers in a sorted index array."""
    return int(np.count_nonzero(idx[1:] - idx[:-1] != 1)) + 1 if len(idx) else 0


#: per thread, the buffers :func:`_take_rows` was asked to keep, by name
_kept = threading.local()


def _take_rows(column, idx, keep=None):
    """``column[idx]`` for a sidecar column: gathered through a byte view,
    which is aligned whatever the column's offset in its file — numpy
    copies an unaligned column whole before it gathers from it (280 MB a
    side at 10M rows, for 3M survivors).

    ``keep``: a name under which the result's memory is kept by this module
    and handed out again to the next call with that name on this thread,
    which overwrites it — for a result of tens of megabytes that is dead by
    then. An array that large is mapped afresh by the allocator when it
    finds no room in its heap, and first touches of fresh pages are what a
    gather into it then costs: 0.062 s for a 59 MB oid column on the
    benchmark's host (3.5 us a page) against 0.017 s for the gather — and
    whether it found room differed from process to process (PERF.md §6,
    PR 35)."""
    column = np.asarray(column)
    row_bytes = column.dtype.itemsize * int(np.prod(column.shape[1:], dtype=np.int64))
    source = column.view(np.uint8).reshape(len(column), row_bytes)
    if keep is None:
        rows = np.take(source, idx, axis=0)
    else:
        kept = getattr(_kept, keep, None)
        if kept is None or kept.size < len(idx) * row_bytes:
            kept = np.empty(len(idx) * row_bytes, dtype=np.uint8)
            setattr(_kept, keep, kept)
        rows = kept[: len(idx) * row_bytes].reshape(len(idx), row_bytes)
        # the indices are row numbers of the column: "clip" only spares
        # numpy the buffered copy that "raise" makes when given an out=
        np.take(source, idx, axis=0, out=rows, mode="clip")
    return rows.view(column.dtype).reshape((len(idx),) + column.shape[1:])


def spatial_prefilter_blocks(old_block, new_block, rect_wsen):
    """Envelope prefilter for a sidecar block pair (both sides must carry
    envelope columns, else None): a key survives in BOTH blocks when EITHER
    side's envelope intersects the filter rectangle — update pairs stay
    aligned, so the classify semantics on the subset equal classifying the
    whole pair then dropping out-of-filter deltas (the reference's
    delta-level filter, kart/base_diff_writer.py:279-341, evaluated on the
    envelope index instead of materialised values).
    -> ((old_sub, new_sub), (old_rows, new_rows)): the survivors as
    unpadded FeatureBlocks of keys and oids, and the row number each
    survivor has in the block it came from (where its envelope and its
    blob's oid are); or None when envelopes are missing.

    Everything after the (block-pruned) envelope scan works on hit *indices*
    rather than full-width masks: the cross-side key propagation probes only
    the hit keys and the compaction gathers only surviving rows, so at 100M
    rows the key/oid pages of out-of-filter regions are never faulted in.
    The survivors are gathered once, into arrays of their own size: the
    device classify pads a block's tail itself. The sub-blocks' oid columns
    are memory this module keeps (:func:`_take_rows`): they hold until the
    next call on this thread, which every caller is done with them by.

    This is the *rows route*: the rule runs before the classify, over every
    row, and the classify reads sub-blocks that name no tree, so none of
    their pages stays on a device. The json-lines filtered route always
    takes it; the filtered count takes it where it shrinks the work — the
    host engine, the mesh, or a census that bounds the rectangle's keep
    share below :data:`CHANGED_ROUTE_MIN_SHARE` — and elsewhere applies the
    same rule behind the classify of the whole pair, to its changed rows
    alone (:func:`changed_rows_in_rect`)."""
    if old_block.envelopes is None or new_block.envelopes is None:
        return None
    from kart_tpu.diff.backend import select_backend
    from kart_tpu.ops.blocks import FeatureBlock

    o_n, n_n = old_block.count, new_block.count
    query = np.asarray(rect_wsen, dtype=np.float64)
    with tm.span("diff.prefilter", rows=max(o_n, n_n)):
        with tm.span("diff.prefilter.scan", rows=o_n + n_n) as sp:
            o_idx = np.flatnonzero(_envelope_hits(old_block, query))
            n_idx = np.flatnonzero(_envelope_hits(new_block, query))
            census = [
                select_backend(b.count).envelope_census(b, query)
                for b in (old_block, new_block)
            ]
            sp.set(
                blocks=census[0][0] + census[1][0],
                blocks_scanned=census[0][1] + census[1][1],
                hits_old=len(o_idx),
                hits_new=len(n_idx),
            )
        o_keys = old_block.keys[:o_n]
        n_keys = new_block.keys[:n_n]
        # propagate hits to the other side's matching keys (both key-sorted):
        # binary-search the keys that hit on one side only into the other
        # side, union the rows found in
        with tm.span("diff.prefilter.propagate") as sp:
            o_hit_keys = _take_rows(o_keys, o_idx)
            n_hit_keys = _take_rows(n_keys, n_idx)
            probed = 0
            if o_n and n_n and not np.array_equal(o_hit_keys, n_hit_keys):
                # (identical hit keys on both sides — edits that don't move
                # geometry, the overwhelmingly common case — need nothing:
                # keys are unique and sorted, so each side's rows matching
                # the other's hit keys ARE its own hit rows, and the
                # binary-search probe storm into the 100M-row key mmaps —
                # scattered page faults at north-star scale — is skipped)
                def found(keys, n, probes):
                    pos = np.minimum(np.searchsorted(keys, probes), n - 1)
                    return pos[np.asarray(keys[pos]) == probes]

                o_only = np.setdiff1d(o_hit_keys, n_hit_keys, assume_unique=True)
                n_only = np.setdiff1d(n_hit_keys, o_hit_keys, assume_unique=True)
                probed = len(o_only) + len(n_only)
                o_idx = np.union1d(o_idx, found(o_keys, o_n, n_only))
                n_idx = np.union1d(n_idx, found(n_keys, n_n, o_only))
                o_hit_keys = _take_rows(o_keys, o_idx)
                n_hit_keys = _take_rows(n_keys, n_idx)
            sp.set(probed=probed)
        with tm.span("diff.prefilter.compact", rows=o_n + n_n) as sp:
            old_sub = FeatureBlock(
                o_hit_keys,
                _take_rows(old_block.oids[:o_n], o_idx, keep="old_oids"),
                None,
                len(o_idx),
            )
            new_sub = FeatureBlock(
                n_hit_keys,
                _take_rows(new_block.oids[:n_n], n_idx, keep="new_oids"),
                None,
                len(n_idx),
            )
            sp.set(
                survivors=len(o_idx) + len(n_idx),
                bytes=sum(
                    a.nbytes
                    for a in (old_sub.keys, old_sub.oids, new_sub.keys, new_sub.oids)
                ),
                runs=_run_count(o_idx) + _run_count(n_idx),
            )
        tm.incr("diff.prefilter.rows_kept", len(o_idx) + len(n_idx))
        return (old_sub, new_sub), (o_idx, n_idx)


#: query-rect pad for the envelope prefilter: sidecar envelopes are rounded
#: to float32 and the filter's envelope to f64, so a borderline feature must
#: ship (fail open) rather than be wrongly withheld — same policy constant
#: as the per-dataset filter transform (spatial_filter/__init__.py)
_PREFILTER_PAD = 1e-4


def _prefilter_rect(spatial_filter_spec):
    """Padded wsen EPSG:4326 rectangle of an active spatial-filter spec, or
    None. The pad keeps the prefilter strictly conservative: anything it
    drops is definitively outside; the writers' exact residue decides the
    boundary cases it lets through."""
    if spatial_filter_spec is None or spatial_filter_spec.match_all:
        return None
    try:
        w, s, e, n = spatial_filter_spec.envelope_wsen_4326
    except Exception:
        return None  # unresolvable filter CRS: fail open (module policy)
    return (
        w - _PREFILTER_PAD,
        max(s - _PREFILTER_PAD, -90.0),
        e + _PREFILTER_PAD,
        min(n + _PREFILTER_PAD, 90.0),
    )


#: The filtered count's changed-rows route (the rectangle test behind the
#: classify of the whole pair, :func:`changed_rows_in_rect`) is taken, where
#: the backend keeps its pages, when the census bounds the rectangle's keep
#: share at or above this. The rows route costs the scan of the blocks the
#: rectangle meets plus, per unit of keep share, the propagate, the
#: compaction and the survivors' classify (on the host below 2M survivors,
#: copied to the device above); the changed-rows route costs the classify of
#: the whole resident pair and its classes' fetch, whatever the rectangle.
#: Measured warm on one TPU v5e, 10M rows a side, 1% of them edited, under
#: rectangles of known keep share (the census bound beside it), rows route
#: against changed-rows route, median seconds a command (PERF.md §5):
#: 1.5% (0.022) 0.030-0.032 against 0.050; 3% (0.043) 0.038 against 0.051;
#: 6% (0.076) 0.049-0.051 against 0.053-0.054; 12% (0.144) 0.072-0.074
#: against 0.061-0.062; the filtered cell's polygon, 29.4% (0.338),
#: 0.130-0.133 against 0.069-0.070. The routes tie near a bound of 0.09.
CHANGED_ROUTE_MIN_SHARE = 0.09


def block_census(block, query):
    """The sidecar's block census of ``block`` against ``query`` -> (an
    upper bound on the share of its rows whose envelope meets ``query``,
    the BLOCK_* class of each aggregate block or None without aggregates).
    The bound is the rows of the blocks not called all-out
    (:func:`kart_tpu.ops.bbox.classify_env_blocks_np`, the block-pruned
    scan's own classes) over the rows, 1.0 without aggregates; O(aggregate
    blocks)."""
    from kart_tpu.ops.bbox import BLOCK_ALL_OUT, classify_env_blocks_np

    n = block.count
    if block.env_blocks is None:
        return (1.0 if n else 0.0), None
    agg, flags, block_rows = block.env_blocks
    cls = classify_env_blocks_np(agg, flags, query)
    if n == 0:
        return 0.0, cls
    met = np.flatnonzero(cls != BLOCK_ALL_OUT)
    rows = len(met) * block_rows
    if len(met) and met[-1] == len(cls) - 1:
        rows -= len(cls) * block_rows - n  # the last block is short
    return rows / n, cls


def _changed_route_census(backend, old_block, new_block, query):
    """Is the filtered count's rectangle test to run behind the classify?
    Only where ``backend`` keeps the revisions' pages on the device (else
    the rows route shrinks the host's or the mesh's work and nothing is
    asked); there the census (span ``diff.prefilter`` > child
    ``diff.prefilter.census``: ``blocks``, ``bound_share``) bounds the
    larger side's keep share, which must reach
    :data:`CHANGED_ROUTE_MIN_SHARE`. The route is counted under
    ``diff.prefilter.route{where=rows|changed}``. -> both sides' block
    classes (:func:`block_census`) where the changed-rows route is taken,
    else None."""
    n = max(old_block.count, new_block.count)
    chosen = None
    if backend.keeps_pages(n):
        with tm.span("diff.prefilter", rows=n):
            with tm.span("diff.prefilter.census") as sp:
                census = [block_census(b, query) for b in (old_block, new_block)]
                bound = max(share for share, _ in census)
                sp.set(
                    blocks=sum(len(cls) for _, cls in census if cls is not None),
                    bound_share=bound,
                )
        if bound >= CHANGED_ROUTE_MIN_SHARE:
            chosen = tuple(cls for _, cls in census)
    tm.incr("diff.prefilter.route", where="rows" if chosen is None else "changed")
    return chosen


def _rows_meet(block, rows, query, block_classes):
    """bool per row of ``rows``: does its float32 envelope meet ``query``?
    As the block-pruned scan decides it, bit for bit: a row of a block the
    census called all-in or all-out takes its block's answer, and only the
    rows of boundary blocks read their envelopes from the sidecar column
    (the changed rows are spread over the whole layer: read one by one,
    they would fault in every page of it)."""
    from kart_tpu.native import bbox_intersects_f32
    from kart_tpu.ops.bbox import BLOCK_ALL_IN, BLOCK_BOUNDARY

    if block_classes is None:
        read = np.arange(len(rows))
        hits = np.zeros(len(rows), dtype=bool)
    else:
        by_block = block_classes[rows // block.env_blocks[2]]
        read = np.flatnonzero(by_block == BLOCK_BOUNDARY)
        hits = by_block == BLOCK_ALL_IN
    hits[read] = bbox_intersects_f32(_take_rows(block.envelopes, rows[read]), query)
    return hits, len(read)


def changed_rows_in_rect(old_block, new_block, classes, changed, query, block_classes):
    """The prefilter's survivors rule (:func:`spatial_prefilter_blocks`)
    applied behind the classify of the whole pair, to its changed rows
    alone: each side's changed rows against the padded rectangle by the
    scan's own predicate (:func:`_rows_meet`: float32 envelopes against the
    float64 query, so a borderline feature fails open exactly as there). A
    DELETE survives where its old envelope meets the rectangle, an INSERT
    where its new one does, and the k-th UPDATE of the old side with the
    k-th of the new (both key-sorted) where either does — the changed rows
    of the keys the rows route keeps, and no other. ``classes`` /
    ``changed``: the whole blocks' class arrays and :func:`changed_indices`
    of them; ``block_classes``: both sides' census
    (:func:`_changed_route_census`). Span ``diff.prefilter`` > child
    ``diff.prefilter.changed`` (``rows`` tested, ``envelopes_read``,
    ``survivors``). -> for old and new, (the surviving changed rows, as row
    numbers of the block, bool per row: is it an UPDATE)."""
    from kart_tpu.ops.diff_kernel import UPDATE

    with tm.span("diff.prefilter", rows=max(old_block.count, new_block.count)):
        with tm.span("diff.prefilter.changed") as sp:
            sides, read = [], 0
            for block, cls, rows, census in zip(
                (old_block, new_block), classes, changed, block_classes
            ):
                hits, side_read = _rows_meet(block, rows, query, census)
                sides.append((rows, cls[rows] == UPDATE, hits))
                read += side_read
            (_, old_upd, old_hit), (_, new_upd, new_hit) = sides
            either = old_hit[old_upd] | new_hit[new_upd]
            old_hit[old_upd] = either
            new_hit[new_upd] = either
            survivors = [(rows[hit], upd[hit]) for rows, upd, hit in sides]
            sp.set(
                rows=sum(len(rows) for rows in changed),
                envelopes_read=read,
                survivors=sum(len(rows) for rows, _ in survivors),
            )
    return survivors


def _feature_diff_routed(base_ds, target_ds, ds_filter=None, spatial_filter_spec=None):
    """Engine selection for the real CLI path: when both revisions have a
    columnar sidecar (O(1) mmap loads), classification runs as the vectorized
    (device) join; otherwise the O(changed) host tree-walk. Force with
    KART_DIFF_ENGINE=columnar|tree. An active repo spatial filter prefilters
    envelope-carrying block pairs before the classify (scan less, BASELINE
    config #4); blocks without envelope columns fall through to the writers'
    value-level filter."""
    import os

    from kart_tpu.diff import sidecar

    base_tree = base_ds.feature_tree if base_ds is not None else None
    target_tree = target_ds.feature_tree if target_ds is not None else None
    if (base_tree.oid if base_tree else None) == (
        target_tree.oid if target_tree else None
    ):
        # identical trees (the usual `kart status`/WC-diff base): O(1),
        # never a full-dataset classify
        return DeltaDiff()

    mode = os.environ.get("KART_DIFF_ENGINE", "auto")
    if mode != "tree" and base_ds is not None and target_ds is not None:
        repo = base_ds.repo or target_ds.repo
        if repo is not None and (
            mode == "columnar"
            or (sidecar.has_sidecar(repo, base_ds) and sidecar.has_sidecar(repo, target_ds))
        ):
            # unpadded mmap views: the host engine and the sharded device
            # path consume count-sliced views, and the one-device route
            # pads chunk by chunk inside classify_blocks — at 100M the
            # two eager padded copies were ~5.6GB of memcpy before any work
            old_block = sidecar.ensure_block(repo, base_ds, pad=False)
            if old_block is not None:
                # big diff plausible: overlap the (async) backend probe
                # with the second sidecar load and the prefilter
                from kart_tpu.diff.backend import warm_probe

                warm_probe(old_block.count)
            new_block = sidecar.ensure_block(repo, target_ds, pad=False)
            if old_block is not None and new_block is not None:
                rect = _prefilter_rect(spatial_filter_spec)
                if rect is not None and base_ds.path_encoder.scheme == "int":
                    filtered = spatial_prefilter_blocks(old_block, new_block, rect)
                    if filtered is not None:
                        # the writers refine delta by delta, on the values
                        (old_block, new_block), _ = filtered
                return get_feature_diff_columnar(
                    base_ds, target_ds, ds_filter, blocks=(old_block, new_block)
                )
    return get_feature_diff(base_ds, target_ds, ds_filter)


def _envelope_verdicts(sf, spatial_filter_spec, block, rows):
    """ENV_* verdict of the filter polygon on the sidecar envelope of each of
    ``rows`` (row numbers of ``block``). The envelope column is float32,
    rounded to nearest, so an envelope is judged padded by the prefilter's
    pad: wholly inside the polygon even so, the geometry in it intersects
    the filter; wholly outside, it cannot. Everything else is ENV_PARTIAL,
    as is every row when the polygon does not lie in the envelope column's
    CRS (a reprojected or projected filter): the geometry decides."""
    from kart_tpu.spatial_filter import ENV_PARTIAL, polygon_set_env_relations

    if sf.reprojected or not spatial_filter_spec.crs.is_geographic:
        return np.full(len(rows), ENV_PARTIAL, dtype=np.uint8)
    env = _take_rows(block.envelopes, rows).astype(np.float64)  # w s e n
    return polygon_set_env_relations(
        sf.filter_parts(),
        env[:, 0] - _PREFILTER_PAD, env[:, 2] + _PREFILTER_PAD,
        env[:, 1] - _PREFILTER_PAD, env[:, 3] + _PREFILTER_PAD,
    )


def _blob_matches(sf, ds, block, row):
    """The exact answer for one row: its blob's geometry against the filter.
    A blob that is not here (promised) cannot be tested and matches, as in
    the writers' delta filter."""
    from kart_tpu.core.odb import ObjectMissing, ObjectPromised
    from kart_tpu.ops.blocks import unpack_oid_hex
    from kart_tpu.spatial_filter import MatchResult

    oid_hex = unpack_oid_hex(np.asarray(block.oids[row : row + 1]))[0]
    try:
        feature = ds.get_feature_promise_from_oid((int(block.keys[row]),), oid_hex)()
    except (ObjectPromised, ObjectMissing):
        return True
    return sf.match_result(feature) is MatchResult.MATCHED


def refine_changed_count(spatial_filter_spec, sides):
    """Exact count of the changed features that match the spatial filter,
    from the changed rows the rectangle kept. ``sides``: for old and new,
    (dataset, the whole sidecar block, the changed rows to refine as row
    numbers of it in key order, bool per row: is it an UPDATE). The sidecar
    envelope decides the rows wholly inside or outside the filter polygon,
    the blob's geometry the residue. An update counts once, and counts if
    either of its sides matches (the reference's delta filter,
    kart/base_diff_writer.py:279-341)."""
    from kart_tpu.spatial_filter import ENV_CONTAINS, ENV_DISJOINT, ENV_PARTIAL

    with tm.span(
        "diff.refine", candidates=sum(len(rows) for _, _, rows, _ in sides)
    ) as sp:
        # one filter for both sides of a delta, the new side's dataset first
        # (as the writers resolve it)
        sf = spatial_filter_spec.resolve_for_dataset(sides[1][0])
        inside = outside = blobs_read = 0
        matched = []
        for ds, block, rows, _ in sides:
            if not sf:  # no geometry column, or no way into its CRS: all match
                inside += len(rows)
                matched.append(np.ones(len(rows), dtype=bool))
                continue
            verdict = _envelope_verdicts(sf, spatial_filter_spec, block, rows)
            match = verdict == ENV_CONTAINS
            inside += int(np.count_nonzero(verdict == ENV_CONTAINS))
            outside += int(np.count_nonzero(verdict == ENV_DISJOINT))
            for i in np.flatnonzero(verdict == ENV_PARTIAL):
                match[i] = _blob_matches(sf, ds, block, int(rows[i]))
                blobs_read += 1
            matched.append(match)
        sp.set(
            inside=inside, outside=outside, residue=blobs_read,
            blobs_read=blobs_read,
        )
        tm.incr("diff.refine.residue_rows", blobs_read)
        # both sides are key-sorted: the k-th update of one is the k-th of
        # the other
        old_match, new_match = matched
        old_upd, new_upd = (upd for _, _, _, upd in sides)
        return int(
            np.count_nonzero(old_match[~old_upd])
            + np.count_nonzero(new_match[~new_upd])
            + np.count_nonzero(old_match[old_upd] | new_match[new_upd])
        )


def _classes_and_changed(backend, old_block, new_block):
    """``backend``'s class arrays of a pair and :func:`changed_indices` of
    them, under the ``diff.classify`` span (``counts_only`` false: the
    classes come home)."""
    from kart_tpu.diff.backend import classify_span
    from kart_tpu.ops.diff_kernel import changed_indices

    with classify_span(backend, old_block, new_block):
        classes = backend.classify(old_block, new_block)[:2]
        return classes, changed_indices(*classes)


def get_dataset_feature_count_fast(
    base_rs, target_rs, ds_path, spatial_filter_spec=None
):
    """Exact changed-feature count for one dataset straight from the
    classify kernel — no Delta/KeyValue objects (`-o feature-count` at
    north-star scale would otherwise build ~1M deltas only to len() them;
    reference analog: exact diff estimation, kart/diff_estimation.py:51-76).

    With an active spatial_filter_spec the count requires envelope sidecar
    columns; otherwise returns None so the delta path can apply the
    value-level filter. The filtered count is exact, the number of features
    `-o json-lines` lists: the changed rows a key of which has an envelope
    meeting the filter's padded bounding rectangle are refined against the
    filter geometry (:func:`refine_changed_count`: envelope verdicts, blob
    reads for the residue; NULL and empty geometries match, a blob that is
    promised matches). Where the rectangle test runs is chosen from what can
    be observed (:func:`_changed_route_census`), one answer either way:

    * the *changed-rows route*, where the backend keeps the revisions' pages
      on the device and the sidecar's block census bounds the keep share at
      or above :data:`CHANGED_ROUTE_MIN_SHARE` (the constant's measurement
      is beside it): the whole pair is classified on the pages the device
      holds — after a process's first command nothing is copied — and the
      rule is applied to the changed rows alone
      (:func:`changed_rows_in_rect`). The trade: a process's first filtered
      command is a page-store miss and copies both whole revisions (28 B a
      row), not the survivors alone;
    * the *rows route* everywhere else (the host engine, the mesh, a small
      rectangle): the envelope prefilter drops the rows outside the
      rectangle before the classify (:func:`spatial_prefilter_blocks`) and
      the survivors are classified.

    A hash-keyed dataset (msgpack/hash paths) is counted from the classify
    after two guards: no side may hold a key twice or the padding key
    (:func:`key_guard`), and every UPDATE pair must name one path on both
    sides (the backend's ``guarded_counts``: on the one-device route the
    pairs are compared on the device, beside the classify; else
    :func:`host_guard` over the classes). Either failing, the delta path answers,
    exactly.

    -> int, or None when the count can't be taken from the columnar route
    with delta-path parity (dataset added/removed, a hash-keyed collision,
    a spatially filtered hash-keyed dataset, missing sidecars, or the
    engine forced to the tree walk)."""
    import os

    from kart_tpu.diff import sidecar

    if os.environ.get("KART_DIFF_ENGINE", "auto") == "tree":
        return None
    base_ds = base_rs.datasets.get(ds_path) if base_rs is not None else None
    target_ds = target_rs.datasets.get(ds_path) if target_rs is not None else None
    if base_ds is None or target_ds is None:
        return None  # whole-dataset add/delete: the delta path handles it
    base_tree = base_ds.feature_tree
    target_tree = target_ds.feature_tree
    if (base_tree.oid if base_tree is not None else None) == (
        target_tree.oid if target_tree is not None else None
    ):
        return 0
    if any(getattr(ds, "path_encoder", None) is None for ds in (base_ds, target_ds)):
        return None
    hash_keyed = _hash_keyed(base_ds) or _hash_keyed(target_ds)
    repo = base_ds.repo or target_ds.repo
    if repo is None:
        return None
    if not (sidecar.has_sidecar(repo, base_ds) and sidecar.has_sidecar(repo, target_ds)):
        return None
    rect = _prefilter_rect(spatial_filter_spec)
    if hash_keyed and rect is not None:
        return None  # the filtered count's refine reads pks: int-pk only
    # no padded copies: the host engine and the sharded device path
    # consume count-sliced mmap views, and the one-device route pads chunk
    # by chunk inside classify_blocks (at 100M the two padded copies were
    # ~5.6GB of memcpy before any classification work)
    old_block = sidecar.load_block(repo, base_ds, pad=False)
    if old_block is not None:
        from kart_tpu.diff.backend import warm_probe

        warm_probe(old_block.count)
    new_block = sidecar.load_block(repo, target_ds, pad=False)
    if old_block is None or new_block is None:
        return None

    from kart_tpu.diff.backend import classify_span, select_backend
    from kart_tpu.ops.diff_kernel import UPDATE

    backend = select_backend(max(old_block.count, new_block.count))
    if rect is not None:
        if old_block.envelopes is None or new_block.envelopes is None:
            return None  # no envelope columns: delta path applies the filter
        query = np.asarray(rect, dtype=np.float64)
        block_classes = _changed_route_census(backend, old_block, new_block, query)
        if block_classes is not None:
            # the whole pair, on the pages the device keeps
            classes, changed = _classes_and_changed(backend, old_block, new_block)
            (old_rows, old_upd), (new_rows, new_upd) = changed_rows_in_rect(
                old_block, new_block, classes, changed, query, block_classes
            )
        else:
            (old_sub, new_sub), (old_rows, new_rows) = spatial_prefilter_blocks(
                old_block, new_block, rect
            )
            classes, changed = _classes_and_changed(
                select_backend(max(old_sub.count, new_sub.count)), old_sub, new_sub
            )
            old_rows, new_rows = old_rows[changed[0]], new_rows[changed[1]]
            old_upd, new_upd = (cls[idx] == UPDATE for cls, idx in zip(classes, changed))
        return refine_changed_count(
            spatial_filter_spec,
            (
                (base_ds, old_block, old_rows, old_upd),
                (target_ds, new_block, new_rows, new_upd),
            ),
        )

    if hash_keyed:
        # the counts and the cross-version guard: on the device beside the
        # classify where the backend can, else the classes come home
        if key_guard(old_block, new_block):
            return None
        counts, verdict = backend.guarded_counts(old_block, new_block)
        if not guard_passed(verdict):
            return None
    else:
        with classify_span(backend, old_block, new_block, counts_only=True):
            counts = backend.counts(old_block, new_block)
    return counts["inserts"] + counts["updates"] + counts["deletes"]


def get_feature_diff_rows(base_rs, target_rs, ds_path):
    """Columnar full-output row plan for one dataset: the classify kernel's
    changed set as (pk, old row, new row) index arrays over the sidecar
    blocks, skipping Delta/KeyValue/DeltaDiff construction entirely (~6us
    of object machinery per delta at 1M-changed scale). The fused
    json-lines writer streams blob data for these rows through the native
    batch inflate and serialises in place — the "fused materialisation"
    pipeline. Row order is sorted-by-pk, identical to the delta path's
    ``sorted_items``.

    -> {"count": m, "pks" int64 (m,), "old_rows"/"new_rows" int64 (m,)
    (row index into the block, -1 for the absent side), "old_block"/
    "new_block", "base_ds"/"target_ds"}, or None when the columnar route
    can't serve it with delta-path parity (dataset added/removed,
    hash-keyed identities, missing sidecars, or the engine forced to the
    tree walk)."""
    import os

    from kart_tpu.diff import sidecar

    if os.environ.get("KART_DIFF_ENGINE", "auto") == "tree":
        return None
    base_ds = base_rs.datasets.get(ds_path) if base_rs is not None else None
    target_ds = target_rs.datasets.get(ds_path) if target_rs is not None else None
    if base_ds is None or target_ds is None:
        return None
    base_tree = base_ds.feature_tree
    target_tree = target_ds.feature_tree
    if (base_tree.oid if base_tree is not None else None) == (
        target_tree.oid if target_tree is not None else None
    ):
        return {"count": 0}
    for ds in (base_ds, target_ds):
        enc = getattr(ds, "path_encoder", None)
        if enc is None or enc.scheme != "int":
            return None  # hash-keyed: collision guards need the delta path
    repo = base_ds.repo or target_ds.repo
    if repo is None:
        return None
    if not (sidecar.has_sidecar(repo, base_ds) and sidecar.has_sidecar(repo, target_ds)):
        return None
    old_block = sidecar.load_block(repo, base_ds, pad=False)
    new_block = sidecar.load_block(repo, target_ds, pad=False)
    if old_block is None or new_block is None:
        return None

    from kart_tpu.diff.backend import classify_span, select_backend
    from kart_tpu.ops.diff_kernel import changed_indices

    backend = select_backend(max(old_block.count, new_block.count))
    with classify_span(backend, old_block, new_block):
        old_class, new_class, _ = backend.classify(old_block, new_block)
        old_idx, new_idx = changed_indices(old_class, new_class)
    okeys = np.asarray(old_block.keys[old_idx])
    nkeys = np.asarray(new_block.keys[new_idx])
    pks = np.union1d(okeys, nkeys)
    m = len(pks)

    def side_rows(side_keys, side_idx):
        rows = np.full(m, -1, dtype=np.int64)
        if len(side_keys):
            pos = np.searchsorted(side_keys, pks)
            posc = np.minimum(pos, len(side_keys) - 1)
            has = (pos < len(side_keys)) & (side_keys[posc] == pks)
            rows[has] = side_idx[posc[has]]
        return rows

    return {
        "count": m,
        "pks": pks,
        "old_rows": side_rows(okeys, old_idx),
        "new_rows": side_rows(nkeys, new_idx),
        "old_block": old_block,
        "new_block": new_block,
        "base_ds": base_ds,
        "target_ds": target_ds,
    }


def get_meta_diff(base_ds, target_ds, ds_filter=None):
    """DeltaDiff of meta items between two versions of a dataset."""
    meta_filter = ds_filter["meta"] if ds_filter is not None else None
    old_items = base_ds.meta_items() if base_ds else {}
    new_items = target_ds.meta_items() if target_ds else {}
    result = DeltaDiff()
    for name in sorted(old_items.keys() | new_items.keys()):
        if meta_filter is not None and name not in meta_filter:
            continue
        old_value = old_items.get(name)
        new_value = new_items.get(name)
        if old_value == new_value:
            continue
        old = KeyValue((name, old_value)) if old_value is not None else None
        new = KeyValue((name, new_value)) if new_value is not None else None
        result.add_delta(Delta(old, new))
    return result


def get_dataset_diff(
    base_rs, target_rs, ds_path, *, ds_filter=None, include_wc_diff=False,
    working_copy=None, workdir_diff_cache=None, spatial_filter_spec=None
):
    """DatasetDiff for one dataset between two revisions (plus the working
    copy on top when include_wc_diff) (reference: diff_util.py:51-95).

    working_copy: pass the caller's WC instance so per-diff side channels
    (spatial-filter pk conflicts) land on the object the caller holds —
    repo.working_copy constructs a fresh instance per access.

    spatial_filter_spec: the repo's resolved spatial filter; envelope-
    carrying sidecar block pairs are prefiltered before the classify, the
    writers apply the exact per-value residue."""
    base_ds = base_rs.datasets.get(ds_path) if base_rs is not None else None
    target_ds = target_rs.datasets.get(ds_path) if target_rs is not None else None

    diff = DatasetDiff()
    if base_ds is None and target_ds is None:
        return diff
    diff["meta"] = get_meta_diff(base_ds, target_ds, ds_filter)
    diff["feature"] = _feature_diff_routed(
        base_ds, target_ds, ds_filter, spatial_filter_spec
    )

    if include_wc_diff:
        if target_ds is None:
            raise ValueError("Cannot diff working copy against a deleted dataset")
        wc = working_copy if working_copy is not None else target_rs.repo.working_copy
        if wc is not None:
            wc_diff = wc.diff_dataset_to_working_copy(
                target_ds, ds_filter=ds_filter, workdir_diff_cache=workdir_diff_cache
            )
            diff = DatasetDiff.concatenated(diff, wc_diff)
    diff.prune()
    return diff


def get_repo_diff(
    base_rs,
    target_rs,
    *,
    repo_key_filter=None,
    include_wc_diff=False,
    working_copy=None,
    spatial_filter_spec=None,
):
    """RepoDiff between two revisions (reference: diff_util.py:27-50)."""
    repo_key_filter = repo_key_filter or RepoKeyFilter.MATCH_ALL_FILTER()
    base_paths = set(base_rs.datasets.paths()) if base_rs is not None else set()
    target_paths = set(target_rs.datasets.paths()) if target_rs is not None else set()
    all_paths = sorted(base_paths | target_paths)

    repo_diff = RepoDiff()
    for ds_path in all_paths:
        if ds_path not in repo_key_filter:
            continue
        ds_diff = get_dataset_diff(
            base_rs,
            target_rs,
            ds_path,
            ds_filter=repo_key_filter[ds_path],
            include_wc_diff=include_wc_diff,
            working_copy=working_copy,
            spatial_filter_spec=spatial_filter_spec,
        )
        if ds_diff:
            repo_diff[ds_path] = ds_diff
    # dataset diffs are already pruned; only drop datasets left empty
    repo_diff.prune(recurse=False)
    return repo_diff
