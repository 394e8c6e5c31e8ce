"""Vectorized Merkle feature-tree construction for int-pk datasets.

Builds the Datasets-V3 feature tree from (pk, blob-oid) columns as numpy
matrix operations — filenames from the PathEncoder's batch matrix, per-leaf
payloads sliced from one entries buffer, tree objects hashed+deflated
through the native batch IO. Bit-identical to per-path TreeBuilder
construction (tested in tests/test_synth.py) at a fraction of the Python
cost; used by the bulk importer's int-pk fast path and the synthetic-repo
generator (kart_tpu/synth.py). Reference analog: the N x git fast-import
tree build (kart/fast_import.py:286-399).
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

    HOLE = 0xFF
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
    n = plan.n = len(pks)

    fn_bytes, fn_len = _msgpack_single_int_batch(pks)
    b64_mat, b64_len = _b64_batch(fn_bytes, fn_len)
    b64w = b64_mat.shape[1]
    leaf_ids = (pks // encoder.branches) % encoder.max_trees

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
    # upper levels: group child trees by parent prefix, entries
    # "40000 <char>\0" + oid, children sorted by raw char byte
    alpha = encoder.alphabet
    child_ids = np.asarray(child_ids, dtype=np.int64)
    for _level in range(encoder.levels - 1, -1, -1):
        parents = {}
        for cid, coid in zip(child_ids.tolist(), child_oids):
            digit = cid % encoder.branches
            parents.setdefault(cid // encoder.branches, []).append(
                (alpha[digit], coid)
            )
        parent_ids = np.fromiter(parents.keys(), dtype=np.int64, count=len(parents))
        parent_ids.sort()
        payloads = []
        for pid in parent_ids.tolist():
            entries = sorted(parents[pid], key=lambda t: t[0].encode())
            payloads.append(
                b"".join(
                    b"40000 %s\x00" % ch.encode() + bytes.fromhex(oid)
                    for ch, oid in entries
                )
            )
        child_oids = _write_level(odb, payloads)
        child_ids = parent_ids
    assert len(child_oids) == 1
    return child_oids[0]


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
    hexes = b"".join(c.tobytes() for c in leaf_oid_chunks).hex()
    child_oids = [hexes[i : i + 40] for i in range(0, len(hexes), 40)]
    assert len(child_oids) == len(child_ids)
    return build_upper_levels(odb, child_ids, child_oids, encoder)


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
        n_leaves = len(plan.uniq_leaves)
        offsets = np.empty(n_leaves + 1, dtype=np.int64)
        if plan.fixed_width:
            buf = plan.entry_matrix.reshape(-1)
            offsets[0] = 0
            np.cumsum(
                plan.counts * plan.entry_matrix.shape[1], out=offsets[1:]
            )
        else:
            buf = plan.entry_matrix[~plan.hole_mask]
            offsets[:-1] = plan.byte_offsets[plan.first_idx]
            offsets[-1] = plan.byte_offsets[plan.n]
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


