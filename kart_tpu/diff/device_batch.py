"""Device-resident record batches over KCOL sidecar blocks — the batch
loader of the sharded diff backend (ISSUE 6 tentpole; 3DPipe's
host-prepare → device-execute split, arxiv 2604.19982, applied to the
classify hot path).

A sidecar block pair is streamed into device memory as **padded,
fixed-shape record batches**:

* every batch ships exactly ``KART_DEVICE_BATCH_ROWS`` slots per mesh shard
  (keys int64 padded with PAD_KEY, oids uint32 (B, 5) zero-padded) plus a
  validity count — shapes never depend on the data, so XLA compiles the
  classify **once per mesh** and reuses it across batches,
  commits and datasets (the one-device route compiles per bucket size);
* batch boundaries are *key-aligned across both sides*
  (:func:`batch_splits`): a key present in either revision falls in the
  same chunk of both, so per-chunk merge-joins have identical semantics to
  classifying the whole pair — nothing straddles a boundary;
* chunks are dealt round-robin onto the mesh shards and executed with
  ``shard_map`` (PartitionSpec over the ``features`` axis): the classify is
  fully shard-local, only the 3-scalar count vector is ``psum``-reduced
  over the interconnect;
* transfers are double-buffered: ``jax.device_put`` is asynchronous, so
  round ``r+1``'s host→HBM copy overlaps round ``r``'s on-device classify;
* a **full round costs the host no array work** (ISSUE 33): where a side's
  ``S`` chunks of a round each hold exactly ``B`` rows they are ``S x B``
  consecutive rows of its columns, and the round is handed to
  ``device_put`` as a reshaped *view* of those columns — a sidecar's mmap'd
  pages, unaligned and read-only as they are. Only a round that needs
  padding (the ragged last one; a side whose key-aligned chunks come short
  because the commit changed the key set) is packed into fresh arrays.
  Decided per side and per round from the chunk lengths alone
  (:func:`pack_round`); the devices receive byte-identical arrays either
  way. Span attribute ``view_sides`` / ``view_rounds`` and counter
  ``diff.device.view_rounds`` say how often it engaged.

Faults: the ``diff.device_transfer`` point fires at every round's
host→device transfer; an injected (or real) failure aborts the whole device
attempt and the backend falls back to host-native with no partial state —
results are only ever published after the final round drains.
"""

import functools
import logging
import os

import numpy as np

from kart_tpu import faults
from kart_tpu import telemetry as tm
from kart_tpu.ops.blocks import PAD_KEY, batch_splits
from kart_tpu.parallel.mesh import FEATURES_AXIS

L = logging.getLogger("kart_tpu.diff.device_batch")


def _env_int(name, default):
    """Tolerant env knob: a malformed value must never kill the CLI."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        L.warning("ignoring malformed %s=%r", name, os.environ[name])
        return default


#: record-batch capacity (rows per mesh-shard slot). Default favours
#: cache residency: 64 Ki rows = ~4 MB working set per side pair.
DEVICE_BATCH_ROWS = _env_int("KART_DEVICE_BATCH_ROWS", 65536)


def _round_views(keys, oids, splits, chunk0, n_shards, batch_rows):
    """A **full** round of one block side as views of its columns, or None.

    When shard slots ``chunk0 .. chunk0+n_shards-1`` all exist and each holds
    exactly ``batch_rows`` rows, the round's rows are ``n_shards * batch_rows``
    consecutive rows of the column, and the stacked arrays :func:`pack_round`
    would build are already there: a reshape of the slice. No chunk holds
    more than ``batch_rows`` rows (:func:`batch_splits`' capacity), so the
    round's row count alone says every one of them is full. The column has
    to be laid out as the device program reads it (C-contiguous ``int64`` /
    ``(n, 5) uint32``); alignment and writeability do not matter — a
    sidecar's mmap'd sections are unaligned and read-only, and the transfer
    takes them as they are (PERF.md §6, PR 27)."""
    if chunk0 + n_shards > len(splits) - 1:
        return None
    lo = int(splits[chunk0])
    hi = lo + n_shards * batch_rows
    if int(splits[chunk0 + n_shards]) != hi:
        return None
    if not (
        keys.dtype == np.int64
        and keys.flags.c_contiguous
        and oids.dtype == np.uint32
        and oids.flags.c_contiguous
        and oids.shape[1:] == (5,)
    ):
        return None
    return (
        keys[lo:hi].reshape(n_shards, batch_rows),
        oids[lo:hi].reshape(n_shards, batch_rows, 5),
    )


def pack_round(keys, oids, splits, chunk0, n_shards, batch_rows):
    """Stack shard slots ``chunk0 .. chunk0+n_shards-1`` of one block side
    into fixed-shape arrays: (S, B) int64 keys (PAD_KEY padding),
    (S, B, 5) uint32 oids, (S,) int64 validity counts. Chunks beyond the
    plan are empty slots (count 0).

    -> (keys, oids, counts, copied): ``copied`` is the bytes the host wrote
    to make them. A full round (:func:`_round_views`) costs no array work —
    its keys and oids are **views of the caller's columns**, ``copied`` is 0,
    and they stay valid only as long as the columns do. Any other round (the
    ragged last one; a side whose key-aligned chunks come short because the
    commit changed the key set; a column of another dtype or layout) is
    three fresh arrays, padding included."""
    views = _round_views(keys, oids, splits, chunk0, n_shards, batch_rows)
    if views is not None:
        return *views, np.full(n_shards, batch_rows, dtype=np.int64), 0
    k_out = np.full((n_shards, batch_rows), PAD_KEY, dtype=np.int64)
    o_out = np.zeros((n_shards, batch_rows, 5), dtype=np.uint32)
    counts = np.zeros(n_shards, dtype=np.int64)
    n_chunks = len(splits) - 1
    for s in range(n_shards):
        c = chunk0 + s
        if c >= n_chunks:
            break
        lo, hi = int(splits[c]), int(splits[c + 1])
        m = hi - lo
        counts[s] = m
        if m:
            k_out[s, :m] = keys[lo:hi]
            o_out[s, :m] = oids[lo:hi]
    return k_out, o_out, counts, k_out.nbytes + o_out.nbytes + counts.nbytes


def unpack_round(dest, shard_classes, splits, chunk0, n_shards):
    """Scatter one round's (S, B) per-shard classes back into ``dest``
    (block-row order) — the inverse of :func:`pack_round`; exact because
    shard slots are contiguous row ranges of the source block."""
    n_chunks = len(splits) - 1
    arr = np.asarray(shard_classes)
    for s in range(n_shards):
        c = chunk0 + s
        if c >= n_chunks:
            break
        lo, hi = int(splits[c]), int(splits[c + 1])
        if hi > lo:
            dest[lo:hi] = arr[s, : hi - lo]


def roundtrip_arrays(keys, oids, batch_rows, n_shards=1):
    """Test hook: block columns -> padded record batches -> block columns.
    Exercises exactly the pack/unpack pair the classify path uses; the
    property tests pin this to the identity."""
    (splits,), n_chunks = batch_splits((keys,), batch_rows)
    out_keys = np.empty(len(keys), dtype=np.int64)
    out_oids = np.empty((len(keys), 5), dtype=np.uint32)
    for chunk0 in range(0, max(n_chunks, 1), n_shards):
        ks, os_, counts, _ = pack_round(
            keys, oids, splits, chunk0, n_shards, batch_rows
        )
        for s in range(n_shards):
            c = chunk0 + s
            if c >= n_chunks:
                break
            lo, hi = int(splits[c]), int(splits[c + 1])
            assert counts[s] == hi - lo
            out_keys[lo:hi] = ks[s, : counts[s]]
            out_oids[lo:hi] = os_[s, : counts[s]]
            # validity invariant: everything past the count is padding
            assert np.all(ks[s, counts[s] :] == PAD_KEY)
            assert not np.any(os_[s, counts[s] :])
    return out_keys, out_oids


def pack_env_round(env, lo, hi, n_shards, per, fill=np.nan):
    """Envelope rows ``[lo:hi)`` of a (N, 4) f32 column -> 4 fixed-shape
    (S, per) f32 shard batches (w, s, e, n), the spatial join's probe-side
    record batch (ISSUE 16; same deal-contiguous layout as
    :func:`pack_round`, so ``result.reshape(-1)[:hi-lo]`` restores row
    order). Padding rows are NaN: the comparison-only overlap predicate
    can never match them, so padded batches count exactly like unpadded
    ones — the validity-count column the classify batches need is
    unnecessary here."""
    m = hi - lo
    if m > n_shards * per:
        raise ValueError(f"batch of {m} rows exceeds {n_shards}x{per} slots")
    cols = np.full((4, n_shards * per), fill, dtype=np.float32)
    if m:
        cols[:, :m] = np.asarray(env[lo:hi], dtype=np.float32).T
    return [c.reshape(n_shards, per) for c in cols]


def pack_geom_pairs(col_a, ia, col_b, ib):
    """Candidate pairs over two vertex columns -> padded fixed-shape
    segment batches for the exact-refine kernel (ISSUE 20).

    -> dict with ``a``/``b``: 4 int32 (P, S) segment-endpoint arrays
    (x0, y0, x1, y1; zero-padded) + ``a_n``/``b_n`` int32 (P,) valid
    segment counts + ``a_poly``/``b_poly`` bool (P,). S is the bucketed
    max segment count per side (bounds the distinct shapes XLA compiles,
    same reasoning as :func:`kart_tpu.ops.blocks.bucket_size` everywhere
    else). Segment endpoints come from the column's cached
    :meth:`~kart_tpu.geom.VertexColumn.segment_table`, so the fill is
    pure gathers + one fancy-indexed scatter per coordinate — no
    per-feature Python work at all. Padding slots are zeros and masked
    out by the counts, so padded batches refine exactly like unpadded
    ones."""
    from kart_tpu.geom import KIND_POLY, _gather_ranges
    from kart_tpu.ops.blocks import bucket_size

    ia = np.asarray(ia, dtype=np.int64)
    ib = np.asarray(ib, dtype=np.int64)
    p = len(ia)

    def _side(col, idx):
        x0, y0, x1, y1, offs = col.segment_table()
        lo, hi = offs[idx], offs[idx + 1]
        counts = (hi - lo).astype(np.int32)
        cap = bucket_size(int(counts.max(initial=1)), minimum=8)
        cols = [np.zeros((p, cap), dtype=np.int32) for _ in range(4)]
        src, per_pair = _gather_ranges(lo, hi)
        if len(src):
            rows = np.repeat(np.arange(p), per_pair)
            slots = src - np.repeat(lo, per_pair)
            for slab, flat in zip(cols, (x0, y0, x1, y1)):
                slab[rows, slots] = flat[src]
        return cols, counts

    a_cols, a_n = _side(col_a, ia)
    b_cols, b_n = _side(col_b, ib)
    return {
        "a": a_cols,
        "a_n": a_n,
        "a_poly": np.asarray(col_a.kinds[ia] == KIND_POLY),
        "b": b_cols,
        "b_n": b_n,
        "b_poly": np.asarray(col_b.kinds[ib] == KIND_POLY),
    }


@functools.lru_cache(maxsize=16)
def make_batched_classify(mesh, counts_only=False):
    """Jitted shard_map classify for fixed-shape record-batch rounds: the
    sort-join (``ops.diff_kernel._classify_mergesort_core``) on every
    shard, bit-identical to the host engine. Inputs are the stacked
    (S, B[, 5]) outputs of :func:`pack_round`; outputs are per-shard class
    arrays plus the psum-reduced count vector — or, with ``counts_only``,
    the psum'd 3-vector alone (``-o feature-count`` and estimation: the
    per-row classes never leave the devices). Cached per (mesh,
    counts_only), and because batch shapes are fixed, each cache entry
    compiles exactly once."""
    import jax

    from jax.sharding import PartitionSpec as P

    from kart_tpu.ops.diff_kernel import _classify_mergesort_core

    # the function's name is the program's on the device trace
    # (``jit__mesh_classify``): no other program of the repo shares it
    def _mesh_classify(ok, oo, nk, no, oc, nc):
        old_class, new_class, _, counts = _classify_mergesort_core(
            ok[0], oo[0], nk[0], no[0], oc[0], nc[0]
        )
        total = jax.lax.psum(counts, FEATURES_AXIS)
        if counts_only:
            return total
        return old_class[None], new_class[None], total

    jax.config.update("jax_enable_x64", True)  # int64 keys / PAD_KEY
    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _mesh_classify,
        mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=P() if counts_only else (spec, spec, P()),
    )
    return jax.jit(fn)


def classify_blocks_batched(old_block, new_block, mesh=None, batch_rows=None,
                            counts_only=False):
    """Drop-in for ``ops.diff_kernel.classify_blocks`` executed as
    shard_map rounds of device-resident record batches over ``mesh``:
    -> (old_class int8 (n_old,), new_class (n_new,), counts dict), in
    original block-row order, bit-identical to the host engine (pinned by
    tests/test_device_batch.py). With ``counts_only`` the class arrays are
    ``None`` and only the psum'd count vector ever leaves the devices —
    the ``-o feature-count`` path skips ~2 x n bytes of class download and
    host scatter per call.

    Raises on device failure — the backend layer owns the host-native
    fallback, and nothing is published until every round has drained, so a
    mid-stream crash (including an injected ``diff.device_transfer`` fault)
    leaves no partial state.
    """
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.parallel.mesh import make_mesh

    if mesh is None:
        mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    if batch_rows is None:
        batch_rows = DEVICE_BATCH_ROWS

    n_old, n_new = old_block.count, new_block.count
    old_keys = np.asarray(old_block.keys[:n_old])
    new_keys = np.asarray(new_block.keys[:n_new])
    old_oids = old_block.oids
    new_oids = new_block.oids

    fn = make_batched_classify(mesh, counts_only)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    transfer_hook = faults.hook("diff.device_transfer")

    old_class = None if counts_only else np.zeros(n_old, dtype=np.int8)
    new_class = None if counts_only else np.zeros(n_new, dtype=np.int8)
    totals = np.zeros(3, dtype=np.int64)
    in_flight = []  # [(device outputs, chunk0, round)] — at most 2 (double buffer)

    tm.gauge_set("diff.device.shards", n_shards)
    tm.gauge_set("diff.device.batch_rows", batch_rows)

    def _drain():
        # the only wait of the path: np.asarray blocks until the round's
        # program has run, so this span holds whatever of the device's work
        # the host's packing of later rounds did not hide
        out, chunk0, r = in_flight.pop(0)
        with tm.span("diff.device.fetch", round=r) as fetch:
            if counts_only:
                counts = np.asarray(out)
                fetch.set(bytes=counts.nbytes)
            else:
                oc, nc, counts = (np.asarray(a) for a in out)
                fetch.set(bytes=oc.nbytes + nc.nbytes + counts.nbytes)
                unpack_round(old_class, oc, old_splits, chunk0, n_shards)
                unpack_round(new_class, nc, new_splits, chunk0, n_shards)
            totals[:] += counts

    h2d_bytes = 0
    view_rounds = 0
    full_counts = None  # a full round-side's count vector, on the mesh

    def _put_side(keys, oids, counts, copied):
        """One side of a round onto the mesh -> (its three device arrays,
        the bytes put). ``device_put`` is asynchronous and nothing waits for
        it here: round r+1's copy overlaps round r's program. Where keys
        and oids are views they alias the block's own storage — a sidecar's
        mapping: the blocks are this call's arguments and every round is
        drained before it returns, so the pages outlive every transfer. A
        full side's counts are ``batch_rows`` on every shard, every round:
        put once a command, the same device array handed to each."""
        nonlocal full_counts
        host = [keys, oids]
        if copied or full_counts is None:
            host.append(counts)
        side = [jax.device_put(a, sharding) for a in host]
        if not copied:
            if len(side) == 3:
                full_counts = side[2]
            else:
                side.append(full_counts)
        return side, sum(a.nbytes for a in host)

    with tm.span(
        "diff.device.classify",
        rows=int(max(n_old, n_new)),
        shards=n_shards,
        batch_rows=batch_rows,
        counts_only=bool(counts_only),
        kernel="sort",
    ) as root:
        with tm.span("diff.device.splits") as splits:
            (old_splits, new_splits), n_chunks = batch_splits(
                (old_keys, new_keys), batch_rows
            )
            splits.set(chunks=n_chunks)
        n_rounds = max(-(-n_chunks // n_shards), 1)
        root.set(rounds=n_rounds, chunks=n_chunks)
        for r in range(n_rounds):
            chunk0 = r * n_shards
            with tm.span("diff.device.pack", round=r) as pack:
                old_host = pack_round(
                    old_keys, old_oids, old_splits, chunk0, n_shards, batch_rows
                )
                new_host = pack_round(
                    new_keys, new_oids, new_splits, chunk0, n_shards, batch_rows
                )
                # what the host copied: nothing for a side of views
                copied = (old_host[3], new_host[3])
                view_sides = copied.count(0)
                pack.set(bytes=sum(copied), view_sides=view_sides)
            view_rounds += view_sides == 2
            # the span is the enqueue; its bytes are what was put
            with tm.span("diff.device.transfer", round=r) as transfer:
                if transfer_hook is not None:
                    transfer_hook()
                (ok, oo, oc), old_put = _put_side(*old_host)
                (nk, no, nc), new_put = _put_side(*new_host)
                transfer.set(bytes=old_put + new_put)
            h2d_bytes += old_put + new_put
            with tm.span("diff.device.kernel", round=r, program="mesh_classify"):
                # the enqueue only; the wait is in the fetch
                out = fn(ok, oo, nk, no, oc, nc)
            in_flight.append((out, chunk0, r))
            if len(in_flight) >= 2:
                _drain()
        while in_flight:
            _drain()
        root.set(bytes=h2d_bytes, view_rounds=view_rounds)

    tm.incr("diff.device.batches", n_rounds * n_shards)
    tm.incr("diff.device.rounds", n_rounds)
    tm.incr("diff.device.h2d_bytes", h2d_bytes)
    tm.incr("diff.device.view_rounds", view_rounds)
    return (
        old_class,
        new_class,
        {
            "inserts": int(totals[0]),
            "updates": int(totals[1]),
            "deletes": int(totals[2]),
        },
    )
