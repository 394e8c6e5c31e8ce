"""Vectorized Merkle feature-tree construction.

Builds the Datasets-V3 feature tree from (pk, blob-oid) columns as numpy
matrix operations — filenames from the PathEncoder's batch matrix, per-leaf
payloads sliced from one entries buffer, tree objects hashed+deflated
through the native batch IO, the upper levels a level at a time as one
fixed-width entry matrix. Bit-identical to per-path TreeBuilder
construction (tested in tests/test_synth.py and tests/test_hash_keyed.py)
at a fraction of the Python cost; used by the bulk importer (int-pk and
msgpack/hash layouts), the merge's apply and the synthetic-repo generator
(kart_tpu/synth.py). Reference analog: the N x git fast-import tree build
(kart/fast_import.py:286-399).
"""

import contextlib
import itertools

import numpy as np

from kart_tpu import telemetry as tm
from kart_tpu.models.paths import PathEncoder
from kart_tpu.utils import map_in_order, pool_workers

_TREE_BATCH = 65536


class TreePlan:
    """Everything about a feature set's tree layout that doesn't depend on
    the blob oids: the sorted order, the entry matrix with names filled in,
    oid cell positions, and the leaf grouping. Built once per pk set, then
    :func:`emit_feature_tree` stamps an oid column in and writes the trees —
    the second (edited) commit reuses the plan and rewrites only the leaves
    its edits touch."""

    __slots__ = (
        "encoder",
        "n",
        "order",
        "entry_matrix",
        "oid_cols",
        "hole_mask",
        "fixed_width",
        "leaf_ids",
        "uniq_leaves",
        "first_idx",
        "counts",
        "byte_offsets",
        "row_of_leaf",
    )


def plan_int_feature_tree(pks, encoder=None):
    """Sorted, name-resolved tree layout for an int-pk feature set.
    pks must be unique int64 (any order)."""
    from kart_tpu.models.paths import _b64_batch, _msgpack_single_int_batch

    encoder = encoder or PathEncoder.INT_PK_ENCODER
    assert encoder.group_length == 1, "upper-level builder assumes 1-char tree names"
    plan = TreePlan()
    plan.encoder = encoder
    pks = np.asarray(pks, dtype=np.int64)
    if pks.size > 1 and (pks[1:] > pks[:-1]).all():
        # already strictly increasing (the importer's ORDER BY pk stream):
        # skip the argsort, one O(n) check
        srt = np.arange(pks.size)
    else:
        srt = np.argsort(pks, kind="stable")
    pks = np.ascontiguousarray(pks[srt])

    fn_bytes, fn_len = _msgpack_single_int_batch(pks)
    b64_mat, b64_len = _b64_batch(fn_bytes, fn_len)
    leaf_ids = (pks // encoder.branches) % encoder.max_trees
    return _plan_named(plan, b64_mat, b64_len, leaf_ids, srt)


def _plan_named(plan, b64_mat, b64_len, leaf_ids, srt, last_wins=False):
    """The layout of rows named ``b64_mat`` (row ``i`` its first
    ``b64_len[i]`` bytes) in the leaves ``leaf_ids``; ``srt`` maps the rows
    to the caller's. ``last_wins``: of rows with one leaf and one name, the
    last alone is kept (a tree builder's insert over an insert)."""
    HOLE = 0xFF
    n = len(leaf_ids)
    b64w = b64_mat.shape[1]

    # sort by (leaf, name-bytes): git tree order; zero-padding the key
    # reproduces "a name that is a prefix of another sorts first"
    name_key = b64_mat.copy()
    if n and int(b64_len.min()) < b64w:
        name_key[np.arange(b64w)[None, :] >= b64_len[:, None]] = 0
    pad_to = (-b64w) % 8
    if pad_to:
        name_key = np.concatenate(
            [name_key, np.zeros((n, pad_to), dtype=np.uint8)], axis=1
        )
    words = np.ascontiguousarray(name_key).view(">u8")  # big-endian words
    order = np.lexsort(
        tuple(words[:, i] for i in range(words.shape[1] - 1, -1, -1))
        + (leaf_ids,)
    )
    if last_wins and n > 1:
        # the sort is stable: of equal rows the last sorts last
        w, lf = words[order], leaf_ids[order]
        same_next = (lf[1:] == lf[:-1]) & (w[1:] == w[:-1]).all(axis=1)
        order = order[~np.append(same_next, False)]
        n = len(order)
    plan.n = n
    plan.order = srt[order]  # original-row -> sorted-row permutation
    b64_mat = b64_mat[order]
    b64_len = b64_len[order]
    plan.leaf_ids = leaf_ids = leaf_ids[order]

    uniform = bool((b64_len == b64_len[0]).all()) if n else True
    rows = np.arange(n)
    if uniform:
        # fixed-width fast path (dense int ranges): no holes at all
        L = int(b64_len[0]) if n else 0
        width = 7 + L + 1 + 20
        out = np.zeros((n, width), dtype=np.uint8)
        out[:, :7] = np.frombuffer(b"100644 ", np.uint8)
        out[:, 7 : 7 + L] = b64_mat[:, :L]
        # out[:, 7+L] is already the NUL
        plan.oid_cols = (7 + L + 1) + np.arange(20)[None, :]
        plan.hole_mask = None
        entry_lens = np.full(n, width, dtype=np.int64)
    else:
        width = 7 + b64w + 1 + 20
        out = np.full((n, width), HOLE, dtype=np.uint8)
        out[:, :7] = np.frombuffer(b"100644 ", np.uint8)
        region = out[:, 7 : 7 + b64w]
        region[:] = b64_mat
        region[np.arange(b64w)[None, :] >= b64_len[:, None]] = HOLE
        out[rows, 7 + b64_len] = 0  # the NUL after the name
        plan.oid_cols = (7 + b64_len + 1)[:, None] + np.arange(20)[None, :]
        hole_mask = out == HOLE
        hole_mask[rows[:, None], plan.oid_cols] = False
        plan.hole_mask = hole_mask
        entry_lens = (7 + b64_len + 1 + 20).astype(np.int64)
    plan.entry_matrix = out
    plan.fixed_width = uniform

    # leaf_ids is sorted (the sort's first key): a leaf starts where it changes
    plan.first_idx = np.concatenate(
        [np.zeros(min(n, 1), dtype=np.int64),
         np.flatnonzero(leaf_ids[1:] != leaf_ids[:-1]) + 1]
    )
    plan.uniq_leaves = leaf_ids[plan.first_idx]
    plan.counts = np.diff(np.append(plan.first_idx, n))
    plan.byte_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(entry_lens, out=plan.byte_offsets[1:])
    # sorted-row -> leaf slot (for mapping edited rows to touched leaves)
    plan.row_of_leaf = np.repeat(np.arange(len(plan.counts)), plan.counts)
    return plan


def _stamp_oids(plan, oids_u8):
    """Write the (sorted) blob-oid column into the plan's entry matrix."""
    oids_sorted = np.asarray(oids_u8, dtype=np.uint8)[plan.order]
    if plan.fixed_width:
        plan.entry_matrix[:, plan.oid_cols[0]] = oids_sorted
    else:
        rows = np.arange(plan.n)
        plan.entry_matrix[rows[:, None], plan.oid_cols] = oids_sorted


def _payload_buffer(plan):
    """Every leaf payload of a stamped plan as one buffer (the entry matrix,
    holes left out) and offsets: leaf ``k`` is ``buf[offsets[k]:offsets[k +
    1]]``."""
    offsets = np.empty(len(plan.uniq_leaves) + 1, dtype=np.int64)
    if plan.fixed_width:
        buf = plan.entry_matrix.reshape(-1)
        offsets[0] = 0
        np.cumsum(plan.counts * plan.entry_matrix.shape[1], out=offsets[1:])
    else:
        buf = plan.entry_matrix[~plan.hole_mask]
        offsets[:-1] = plan.byte_offsets[plan.first_idx]
        offsets[-1] = plan.byte_offsets[plan.n]
    return buf, offsets


def _leaf_payloads(plan, touched):
    """Serialised leaf-tree payload bytes for the given leaf slots (the
    entry matrix must already carry the oid column — :func:`_stamp_oids`)."""
    first_idx, counts = plan.first_idx, plan.counts
    if plan.fixed_width:
        buf = plan.entry_matrix  # slice rows directly
        return [
            buf[first_idx[t] : first_idx[t] + counts[t]].tobytes()
            for t in touched.tolist()
        ]
    full = plan.entry_matrix[~plan.hole_mask].tobytes()
    starts = plan.byte_offsets[first_idx]
    ends = plan.byte_offsets[first_idx + counts]
    return [full[starts[t] : ends[t]] for t in touched.tolist()]


def emit_leaf_trees(writer, plan, oids_u8, pks):
    """Stamp the blob oids into ``plan`` and write ONLY its leaf tree
    objects into ``writer`` (a PackWriter); -> [(leaf_tree_path, hex oid)],
    leaf paths relative to the feature root (e.g. ``"A/B/c/D"``).

    The parallel-import worker half of the Merkle build: each worker ships
    whole leaf trees in its own pack, the parent stitches them into the
    dataset spine with the ordinary TreeBuilder (reference analog: the
    N-way fast-import temp-branch merge, kart/fast_import.py:286-399)."""
    n = plan.n
    if n == 0:
        return []
    _stamp_oids(plan, oids_u8)
    touched = np.arange(len(plan.uniq_leaves))
    payloads = _leaf_payloads(plan, touched)
    oids = []
    for i in range(0, len(payloads), _TREE_BATCH):
        oids.extend(writer.add_batch("tree", payloads[i : i + _TREE_BATCH]))
    pks_sorted = np.asarray(pks, dtype=np.int64)[plan.order]
    enc = plan.encoder
    paths = [
        enc.encode_pks_to_path((int(pks_sorted[fi]),)).rpartition("/")[0]
        for fi in plan.first_idx.tolist()
    ]
    return list(zip(paths, oids))


def _write_level(odb, payloads):
    """Batch-write tree objects; -> list of hex oids."""
    oids = []
    for i in range(0, len(payloads), _TREE_BATCH):
        chunk = payloads[i : i + _TREE_BATCH]
        if odb._bulk_writer is not None:
            oids.extend(odb._bulk_writer.add_batch("tree", chunk))
        else:
            oids.extend(odb.write_raw("tree", c) for c in chunk)
    return oids


def emit_feature_tree(odb, plan, oids_u8, *, prev=None):
    """Stamp the blob-oid column into ``plan``'s entry matrix and write the
    tree objects; -> (feature tree hex oid, leaf_oids list).

    ``prev``: optional (leaf_oids, changed_original_rows) from a previous
    emit over the same plan — only leaves containing a changed row are
    rebuilt and written; the rest reuse their oids (the 1%-edit benchmark
    commit touches ~half the leaves at 100M scale)."""
    n = plan.n
    if n == 0:
        return odb.write_tree([]), []
    _stamp_oids(plan, oids_u8)
    rows = np.arange(n)

    uniq, first_idx, counts = plan.uniq_leaves, plan.first_idx, plan.counts
    if prev is not None:
        prev_leaf_oids, changed_rows = prev
        sorted_pos = np.empty(n, dtype=np.int64)
        sorted_pos[plan.order] = rows
        touched = np.unique(plan.row_of_leaf[sorted_pos[changed_rows]])
        leaf_oids = list(prev_leaf_oids)
    else:
        touched = np.arange(len(uniq))
        leaf_oids = [None] * len(uniq)

    payloads = _leaf_payloads(plan, touched)
    new_oids = _write_level(odb, payloads)
    for t, oid in zip(touched.tolist(), new_oids):
        leaf_oids[t] = oid

    root = build_upper_levels(odb, uniq, leaf_oids, plan.encoder)
    return root, leaf_oids


def build_upper_levels(odb, child_ids, child_oids, encoder):
    """Build and write the spine of upper-level trees over already-written
    leaf trees; -> feature-tree root hex oid. ``child_ids``: int64 leaf
    slots (``pk // branches`` space, ascending); ``child_oids``: their hex
    oids. Shared by :func:`emit_feature_tree` and the import pipeline's
    streamed leaf build (identical grouping -> identical tree objects)."""
    child_ids = np.asarray(child_ids, dtype=np.int64)
    oids_u8 = np.frombuffer(bytes.fromhex("".join(child_oids)), dtype=np.uint8)
    return _spine(odb, child_ids, oids_u8.reshape(-1, 20), None, encoder)


#: tree objects a native framing call makes (a batch of the payload column)
_PAYLOAD_BATCH = 262_144
_SUBTREE_ENTRY = 28  # "40000 " + one-character name + NUL + 20-byte oid


def _write_payloads(odb, buf, offsets):
    """Write the tree objects ``buf[offsets[i]:offsets[i + 1]]``; -> their
    (n, 20) uint8 oids. Into a bulk pack they go framed by the native IO
    core a batch at a time, with no bytes object a tree; anywhere else (a
    loose store, :class:`_TreeNamer`) through ``write_raw``."""
    from kart_tpu import native
    from kart_tpu.core.packs import TYPE_CODES

    offsets = np.asarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    out = np.empty((n, 20), dtype=np.uint8)
    writer = getattr(odb, "_bulk_writer", None)
    for i in range(0, n, _PAYLOAD_BATCH):
        j = min(n, i + _PAYLOAD_BATCH)
        lo, hi = int(offsets[i]), int(offsets[j])
        framed = None
        if writer is not None:
            framed = native.pack_records_base(
                "tree", TYPE_CODES["tree"], buf[lo:hi], offsets[i : j + 1] - lo,
                writer.level,
            )
        if framed is not None:
            out[i:j] = writer.append_framed(framed)
            continue
        chunk = bytes(buf[lo:hi])
        bounds = (offsets[i : j + 1] - lo).tolist()
        hexes = _write_level(odb, [chunk[a:b] for a, b in zip(bounds[:-1], bounds[1:])])
        out[i:j] = np.frombuffer(bytes.fromhex("".join(hexes)), dtype=np.uint8).reshape(
            -1, 20
        )
    return out


def _spine(odb, ids, oids_u8, present, encoder, old=None, written=None):
    """The upper-level trees over changed nodes of the deepest level; ->
    feature-root hex oid. ``ids``: the nodes (int64 slots, ascending,
    unique), ``oids_u8`` (n, 20) their new oids, ``present``: which of them
    still exist (None: all). ``old``, for a tree that was there before:
    ``old[d]`` = (slots, oids) of the children the depth-``d`` ancestors of
    ``ids`` had, which stay unless ``ids`` name them. A level at a time:
    each parent's entries ``"40000 <char>\\0" + oid`` sorted by the raw
    character byte, as one fixed-width matrix. ``written``: a list the
    number of trees each level wrote is appended to."""
    branches, alpha = encoder.branches, encoder._alpha_u8
    if present is None:
        present = np.ones(len(ids), dtype=bool)
    for depth in range(encoder.levels - 1, -1, -1):
        parents = np.unique(ids // branches)
        c_ids, c_oids = ids[present], oids_u8[present]
        if old is not None:
            o_ids, o_oids = old[depth]
            stay = ~np.isin(o_ids, ids)
            c_ids = np.concatenate([o_ids[stay], c_ids])
            c_oids = np.concatenate([o_oids[stay], c_oids])
        chars = alpha[c_ids % branches]
        order = np.lexsort((chars, c_ids // branches))
        entries = np.empty((len(order), _SUBTREE_ENTRY), dtype=np.uint8)
        entries[:, :6] = np.frombuffer(b"40000 ", dtype=np.uint8)
        entries[:, 6] = chars[order]
        entries[:, 7] = 0
        entries[:, 8:] = c_oids[order]
        par = c_ids[order] // branches
        first = np.flatnonzero(np.append(True, par[1:] != par[:-1])) if len(par) else par
        offsets = np.append(first, len(par)) * _SUBTREE_ENTRY
        made = _write_payloads(odb, entries.reshape(-1), offsets)
        if written is not None:
            written.append(len(made))
        present = np.isin(parents, par[first])
        oids_u8 = np.zeros((len(parents), 20), dtype=np.uint8)
        oids_u8[present] = made
        ids = parents
    if not present[0]:
        return odb.write_tree([])
    return oids_u8[0].tobytes().hex()


def build_int_feature_tree(odb, pks, oids_u8, encoder=None):
    """Vectorized Merkle build of a Datasets-V3 feature tree for an int-pk
    feature set; -> feature tree hex oid (bit-identical to the tree a real
    import of the same (pk, blob) set produces — tested).

    pks: unique int64 (n,); oids_u8: (n, 20) uint8 blob oids. Writes all
    tree objects into ``odb`` (wrap in ``odb.bulk_pack()`` for scale).
    """
    plan = plan_int_feature_tree(pks, encoder)
    if plan.n == 0:
        return odb.write_tree([])
    oid, _ = emit_feature_tree(odb, plan, oids_u8)
    return oid


class _TreeNamer:
    """Stands in for the object store where a tree's name alone is wanted:
    every tree object is hashed, none is kept."""

    _bulk_writer = None

    @staticmethod
    def write_raw(obj_type, content):
        from kart_tpu.core.objects import hash_object

        return hash_object(obj_type, content)


#: rows a batch of :func:`write_int_feature_tree`'s leaf stream: its arrays
#: (11 MB of payloads, as much again framed) stay under the allocator's
#: 32 MB mmap ceiling, so a batch reuses the heap an earlier one freed (a
#: few batches are in flight at a time, one a pool thread and one more: not
#: the layer). A whole 4M-row column at once asks the kernel for
#: ~1.5 GB of fresh pages a call, which on a shared host is both most of the
#: time and most of its run-to-run spread (PERF.md §6, PR 40)
LEAF_STREAM_ROWS = 262_144


def _ascending_in_range(pks, pk_limit):
    """Are the (not empty) ``pks`` strictly ascending within [0, pk_limit)?
    What the native leaf build needs of a batch (no leaf-id wraparound)."""
    return bool(pks[0] >= 0 and pks[-1] < pk_limit and (pks[1:] > pks[:-1]).all())


def _root_over_leaves(odb, leaf_id_chunks, leaf_oid_chunks, encoder):
    """The spine over leaf trees made batch by batch; -> feature-root hex
    oid. ``leaf_id_chunks``: ascending int64 leaf slots, an array a batch;
    ``leaf_oid_chunks``: their (n, 20) uint8 oids, batch for batch."""
    child_ids = np.concatenate(leaf_id_chunks)
    child_oids = np.concatenate(
        [np.asarray(c, dtype=np.uint8).reshape(-1, 20) for c in leaf_oid_chunks]
    )
    assert len(child_oids) == len(child_ids)
    return _spine(odb, child_ids.astype(np.int64), child_oids, None, encoder)


def _cut_on_leaves(batches, branches):
    """(pks, oids) batches in key order -> the same rows in batches of at
    most ``LEAF_STREAM_ROWS`` rows, none of which ends inside a leaf: each
    can be made into leaf trees on its own, by whichever thread is free. A
    batch's tail is held until the next batch is seen. Where the producer
    cut on a leaf boundary itself (``merge._merged_batches``) every batch
    goes on as it came, a view; where the next batch goes on with the held
    leaf, that leaf's rows move over to it, which copies the next batch.
    Rows out of order are cut somewhere all the same: whoever makes the
    leaves finds them."""
    held = None  # the last piece seen: its last leaf may go on
    for pks, oids_u8 in batches:
        pks = np.asarray(pks, dtype=np.int64)
        oids_u8 = np.asarray(oids_u8, dtype=np.uint8).reshape(-1, 20)
        if not len(pks):
            continue
        if held is not None:
            h_pks, h_oids = held
            if h_pks[-1] // branches == pks[0] // branches:
                cut = int(np.searchsorted(h_pks, h_pks[-1] // branches * branches))
                pks = np.concatenate([h_pks[cut:], pks])
                oids_u8 = np.concatenate([h_oids[cut:], oids_u8])
                h_pks, h_oids = h_pks[:cut], h_oids[:cut]
            if len(h_pks):
                yield h_pks, h_oids
        lo = 0
        while len(pks) - lo > LEAF_STREAM_ROWS:
            hi = lo + LEAF_STREAM_ROWS
            cut = lo + int(np.searchsorted(pks[lo:hi], pks[hi] // branches * branches))
            if cut == lo:  # no leaf is that long: the rows are out of order
                cut = hi
            yield pks[lo:cut], oids_u8[lo:cut]
            lo = cut
        held = pks[lo:], oids_u8[lo:]
    if held is not None:
        yield held


def _leaf_batch(batch, encoder, pk_limit):
    """The leaf trees of one batch of :func:`_cut_on_leaves`, made, hashed
    and framed in two GIL-free native calls: -> (leaf ids, framed records
    ``(oids, crcs, records, offsets)``), or None where the stream does not
    apply. Runs on a pool thread, which has no open span: the span names
    its parent itself."""
    from kart_tpu import native
    from kart_tpu.core.packs import TYPE_CODES

    pks, oids_u8 = batch
    with tm.span("merge.leaf_batch", rows=len(pks), parent="merge.apply") as span:
        if not _ascending_in_range(pks, pk_limit):
            return None
        made = native.leaf_payloads(pks, oids_u8, encoder.branches, pk_limit)
        if made is None:
            return None
        buf, offsets, leaf_ids = made
        framed = native.pack_records_base("tree", TYPE_CODES["tree"], buf, offsets, 0)
        if framed is None:
            return None
        span.set(leaves=len(leaf_ids), bytes=len(buf))
    return leaf_ids, framed


def _stream_leaf_trees(batches, encoder):
    """The leaf trees of (pk, oid) columns that come in key order, made and
    named batch by batch in the native IO core, the batches cut on leaf
    boundaries (:func:`_cut_on_leaves`) and handed to a pool of threads
    (:func:`_leaf_batch`; as many as ``pool_workers()`` reads off the host,
    results taken in batch order, at most one more than that in flight): ->
    ([a batch's leaf ids], [a batch's framed records, the first member of
    which is its leaves' oids]), or None where the stream does not apply (no
    native core, pks not strictly ascending or out of the encoder's range,
    in any batch) and the plan has to do it. One worker, or a stream of one
    batch, stays on the calling thread."""
    from kart_tpu import native

    if encoder.scheme != "int" or native.load_io() is None:
        return None
    pk_limit = encoder.branches ** (encoder.levels + 1)
    pieces = _cut_on_leaves(batches, encoder.branches)
    head = list(itertools.islice(pieces, 2))
    pieces = itertools.chain(head, pieces)
    workers = pool_workers() if len(head) == 2 else 1

    def make(batch):
        return _leaf_batch(batch, encoder, pk_limit)

    if workers == 1:
        results = (make(batch) for batch in pieces)
    else:
        results = map_in_order(make, pieces, workers, "kart-leaf")
    leaf_id_chunks, framed = [], []
    last_leaf = -1
    with contextlib.closing(results):
        for result in results:
            # leaf ids ascending from batch to batch: the batches came in
            # key order and none shares a leaf with the one before it
            if result is None or result[0][0] <= last_leaf:
                return None
            leaf_id_chunks.append(result[0])
            framed.append(result[1])
            last_leaf = int(result[0][-1])
    tm.incr("merge.leaf_batches", len(framed))
    tm.annotate_span("merge.apply", leaf_batches=len(framed), workers=workers)
    return leaf_id_chunks, framed


def write_int_feature_tree(odb, batches, encoder=None):
    """:func:`build_int_feature_tree` for a tree that may be there already
    (a merge tried again, a dry run repeated): the tree objects are named
    first, in memory, and written — one pack, stored — only where ``odb``
    does not hold the root. A store holds a tree with all beneath it (a pack
    appears whole, its root written last), so the root answers for the
    rest. ``batches()`` -> the columns as (pks int64, oids (n, 20) uint8)
    pairs, together not empty. Batches in key order take the native leaf
    stream as they come (:func:`_stream_leaf_trees`: nothing of the layer's
    size is held but the trees; a producer that ends its batches where a
    leaf ends saves it a copy); any other columns the plan, whole.
    -> feature tree hex oid."""
    encoder = encoder or PathEncoder.INT_PK_ENCODER
    streamed = _stream_leaf_trees(batches(), encoder)
    if streamed is None:
        pks, oids_u8 = (np.concatenate(column) for column in zip(*batches()))
        plan = plan_int_feature_tree(pks, encoder)
        root, _ = emit_feature_tree(_TreeNamer, plan, oids_u8)
        if not odb.contains(root):
            with odb.bulk_pack(level=0):
                emit_feature_tree(odb, plan, oids_u8)
        return root
    leaf_id_chunks, framed = streamed
    leaf_oids = [records[0] for records in framed]
    root = _root_over_leaves(_TreeNamer, leaf_id_chunks, leaf_oids, encoder)
    if not odb.contains(root):
        with odb.bulk_pack(level=0) as writer:
            for records in framed:
                writer.append_framed(records)
            _root_over_leaves(odb, leaf_id_chunks, leaf_oids, encoder)
    return root


class _NotLaidOut(Exception):
    """A tree that is not the encoder's layout (another encoder wrote it,
    or a hand-made commit): the per-path builder must do it."""


def _parse_subtrees(contents, node_ids, encoder):
    """Raw contents of upper-level trees -> (child slots int64, (m, 20)
    uint8 oids), sorted by slot. Every entry must be a subtree with a
    one-character name of the encoder's alphabet."""
    lens = np.fromiter((len(c) for c in contents), dtype=np.int64, count=len(contents))
    if len(lens) and bool((lens % _SUBTREE_ENTRY).any()):
        raise _NotLaidOut()
    rows = np.frombuffer(b"".join(contents), dtype=np.uint8).reshape(-1, _SUBTREE_ENTRY)
    digit = encoder._alpha_inv[rows[:, 6]]
    if not (
        (rows[:, :6] == np.frombuffer(b"40000 ", dtype=np.uint8)).all()
        and (rows[:, 7] == 0).all()
        and (digit >= 0).all()
    ):
        raise _NotLaidOut()
    ids = np.repeat(np.asarray(node_ids, dtype=np.int64), lens // _SUBTREE_ENTRY)
    ids = ids * encoder.branches + digit
    order = np.argsort(ids, kind="stable")
    return ids[order], np.ascontiguousarray(rows[order, 8:])


def _parse_leaves(contents, leaf_ids):
    """Raw contents of leaf trees -> [(leaf slot, name bytes, oid bytes)]."""
    out = []
    for leaf, content in zip(leaf_ids, contents):
        i = 0
        while i < len(content):
            sp = content.index(b" ", i)
            nul = content.index(b"\x00", sp)
            if content[i:sp] != b"100644":
                raise _NotLaidOut()
            out.append((leaf, content[sp + 1 : nul], content[nul + 1 : nul + 21]))
            i = nul + 21
    return out


def _read_touched(odb, root_oid, leaf_ids, encoder):
    """The parts of the tree ``root_oid`` that a change of the leaves
    ``leaf_ids`` (ascending, unique) rewrites, read top down: -> (``old``
    for :func:`_spine`, the entries those leaves hold now)."""
    old = []
    node_ids, node_oids = np.zeros(1, dtype=np.int64), [root_oid]
    for depth in range(encoder.levels + 1):
        contents = []
        for oid in node_oids:
            kind, content = odb.read_raw(oid)
            if kind != "tree":
                raise _NotLaidOut()
            contents.append(content)
        if depth == encoder.levels:
            return old, _parse_leaves(contents, node_ids.tolist())
        c_ids, c_oids = _parse_subtrees(contents, node_ids, encoder)
        old.append((c_ids, c_oids))
        want = np.unique(leaf_ids // encoder.branches ** (encoder.levels - 1 - depth))
        pos = np.minimum(np.searchsorted(c_ids, want), max(len(c_ids) - 1, 0))
        found = c_ids[pos] == want if len(c_ids) else np.zeros(len(want), dtype=bool)
        node_ids = want[found]
        hexes = c_oids[pos[found]].tobytes().hex()
        node_oids = [hexes[i : i + 40] for i in range(0, len(hexes), 40)]


def write_hash_feature_tree(odb, rows, oids_u8, encoder, *, prev=None, removed=None):
    """The feature tree of a msgpack/hash dataset, written column-wise; ->
    its hex oid, or None where ``prev`` is not laid out by ``encoder`` (the
    caller's per-path builder does it then).

    ``rows``: :class:`kart_tpu.models.paths.HashRows` of the features to
    write, ``oids_u8`` their (n, 20) blob oids; of two rows with one pk the
    last is kept. With ``prev`` (a feature tree's hex oid) the tree is
    ``prev`` less the features ``removed`` (HashRows; pks it does not hold
    are passed over) plus ``rows``: only the leaves those touch, and their
    ancestors, are read and written. Bit-identical to ``TreeBuilder``'s
    ``remove`` of every removed path then ``insert_many`` of the rows
    (tested). Leaves are made as the int layout's are (:func:`_plan_named`:
    git's entry order, one matrix), written through the native framing in
    batches, then the upper levels a level at a time (:func:`_spine`)."""
    written = []
    with tm.span("feature_tree.write", scheme=encoder.scheme, rows=len(rows)) as sp:
        oids_u8 = np.asarray(oids_u8, dtype=np.uint8).reshape(-1, 20)
        old = None
        if prev is None:
            if not len(rows):
                return odb.write_tree([])
            names, lens, leaf_ids = rows.names, rows.name_lens, rows.leaf_ids
        else:
            from kart_tpu.models.paths import ByteRows

            gone = removed if removed is not None else rows.take(slice(0, 0))
            touched = np.unique(np.concatenate([gone.leaf_ids, rows.leaf_ids]))
            if not len(touched):
                return prev
            try:
                old, entries = _read_touched(odb, prev, touched, encoder)
            except _NotLaidOut:
                return None
            drop = set(gone.name_rows().tolist()) | set(rows.name_rows().tolist())
            kept = [e for e in entries if e[1] not in drop]
            mat, lens = ByteRows.from_list(
                [e[1] for e in kept] + rows.name_rows().tolist()
            ).matrix()
            names, leaf_ids = mat, np.concatenate(
                [np.fromiter((e[0] for e in kept), dtype=np.int64, count=len(kept)),
                 rows.leaf_ids]
            )
            oids_u8 = np.concatenate([
                np.frombuffer(b"".join(e[2] for e in kept), dtype=np.uint8).reshape(-1, 20),
                oids_u8,
            ])
        plan = _plan_named(
            TreePlan(), names, lens, leaf_ids, np.arange(len(leaf_ids)), last_wins=True
        )
        plan.encoder = encoder
        if plan.n:
            _stamp_oids(plan, oids_u8)
            buf, offsets = _payload_buffer(plan)
            leaf_oids = _write_payloads(odb, buf, offsets)
        else:
            leaf_oids = np.zeros((0, 20), dtype=np.uint8)
        written.append(len(leaf_oids))
        if old is None:
            ids, present = plan.uniq_leaves, None
        else:
            ids = touched
            present = np.isin(touched, plan.uniq_leaves)
            new_oids = np.zeros((len(touched), 20), dtype=np.uint8)
            new_oids[present] = leaf_oids
            leaf_oids = new_oids
        root = _spine(odb, ids, leaf_oids, present, encoder, old, written)
        sp.set(trees=sum(written))
    return root


class StreamingLeafEmitter:
    """Incremental leaf-tree construction from the import pipeline's sorted
    (pk, blob-oid) stream: :meth:`feed` buffers the trailing partial leaf
    and returns the serialised payloads of every leaf COMPLETED by the
    batch, so leaf hashing/packing overlaps the feature stream instead of
    running as a serial tail after it. Payload bytes are produced by the
    same :func:`plan_int_feature_tree` machinery as the end-of-stream
    build — a leaf's payload depends only on its own rows, so the streamed
    build is bit-identical (property-tested).

    Only valid for strictly-increasing, non-negative pks below
    ``branches ** (levels + 1)`` (no leaf-id wraparound — leaf ids arrive
    in ascending order or not at all). The first violation flips
    :attr:`ok` False and the caller falls back to the end-of-stream
    ``build_int_feature_tree``; leaves already emitted become unreferenced
    pack objects, which is benign (the root oid is rebuilt from the full
    column set)."""

    def __init__(self, encoder=None):
        self.encoder = encoder or PathEncoder.INT_PK_ENCODER
        self.ok = self.encoder.scheme == "int"
        self._pk_limit = self.encoder.branches ** (self.encoder.levels + 1)
        from kart_tpu import native

        self._native = self.ok and native.load_io() is not None
        self._last_pk = None
        self._carry_pks = np.empty(0, dtype=np.int64)
        self._carry_oids = np.empty((0, 20), dtype=np.uint8)
        #: ascending leaf slots emitted so far (list of int64 arrays)
        self.leaf_id_chunks = []

    def _check(self, pks):
        if self._last_pk is not None and pks[0] <= self._last_pk:
            return False
        return _ascending_in_range(pks, self._pk_limit)

    def _payloads(self, pks, oids_u8):
        """Complete-leaf payloads for sorted ``pks`` -> (buf uint8,
        offsets int64 (n_leaves+1,), leaf_ids int64).

        Leaves partition the (leaf, name)-sorted rows contiguously, so the
        concatenated leaf payloads ARE the (hole-compacted) entry matrix —
        no per-leaf bytes objects, no join; the same buffer
        :func:`_leaf_payloads` would produce sliced per leaf (the
        equivalence property tests pin this).

        When the native IO core is present the whole build (msgpack + b64
        names, leaf grouping, in-leaf git name sort, entry emit) runs in
        one GIL-free call (io_leaf_payloads) — it was the import stream's
        largest remaining Python cost. The emitter's :meth:`_check` already
        guarantees what the kernel needs (ascending pks within
        ``branches ** (levels+1)``, so ``pk // branches`` needs no
        ``max_trees`` wrap); the numpy plan below is the fallback and the
        equivalence reference."""
        if self._native:
            from kart_tpu import native

            out = native.leaf_payloads(
                pks, oids_u8, self.encoder.branches, self._pk_limit
            )
            if out is not None:
                self.leaf_id_chunks.append(out[2])
                return out
            self._native = False  # lib lost mid-run: stay on the plan path
        plan = plan_int_feature_tree(pks, self.encoder)
        _stamp_oids(plan, oids_u8)
        buf, offsets = _payload_buffer(plan)
        self.leaf_id_chunks.append(plan.uniq_leaves)
        return buf, offsets, plan.uniq_leaves

    def feed(self, pks, oids_u8):
        """Consume one sorted stream batch; -> (payload_buf, offsets,
        leaf_ids) for the leaves the batch completed, or None (nothing
        completed yet, or the stream turned out not to be streamable —
        check :attr:`ok`)."""
        if not self.ok:
            return None
        pks = np.asarray(pks, dtype=np.int64)
        if pks.size == 0:
            return None
        if not self._check(pks):
            self.ok = False
            return None
        self._last_pk = int(pks[-1])
        oids_u8 = np.asarray(oids_u8, dtype=np.uint8).reshape(-1, 20)
        if self._carry_pks.size:
            pks = np.concatenate([self._carry_pks, pks])
            oids_u8 = np.concatenate([self._carry_oids, oids_u8])
        # rows of the last (possibly still growing) leaf stay buffered
        leaf = pks // self.encoder.branches
        cut = int(np.searchsorted(leaf, leaf[-1]))
        self._carry_pks = pks[cut:]
        self._carry_oids = oids_u8[cut:]
        if cut == 0:
            return None
        return self._payloads(pks[:cut], oids_u8[:cut])

    def finish(self):
        """Payloads of the final partial leaf; -> same shape as
        :meth:`feed` or None."""
        if not self.ok or not self._carry_pks.size:
            return None
        out = self._payloads(self._carry_pks, self._carry_oids)
        self._carry_pks = np.empty(0, dtype=np.int64)
        self._carry_oids = np.empty((0, 20), dtype=np.uint8)
        return out

    def build_root(self, odb, leaf_oids_u8_chunks):
        """Upper spine over the streamed leaves; -> feature-root hex oid.
        ``leaf_oids_u8_chunks``: (n,20) uint8 arrays, one per emitted
        payload batch, in emission order."""
        return _root_over_leaves(
            odb, self.leaf_id_chunks, leaf_oids_u8_chunks, self.encoder
        )


