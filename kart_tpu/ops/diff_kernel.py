"""Vectorized diff classification — reference hot loop #1 as one jitted
merge-join (SURVEY.md §3.1, rich_base_dataset.py:205-300).

Given two FeatureBlocks (sorted key+oid arrays), classification runs
entirely on device with no Python per-feature work, no data-dependent control
flow, and static shapes. Two device kernels with identical semantics:

- ``_classify_mergesort_core`` (the flagship, default on accelerators): one
  3-operand ``lax.sort`` of the concatenated keys (with concat position for
  stability and a 64-bit oid fold as the payload) brings every old/new pair
  of the same key adjacent, then neighbour compares classify all keys at
  once and scatters return classes and partner rows to block order. TPU's
  bitonic sort network is ~20x faster than the log(n) serial gather rounds
  a binary search lowers to. It is far from a pass or two over HBM, though:
  per 10M-row command on a v5e (0.777 s, 0.091% of the memory roof) the
  (n, 5) oid row gather of the exactness re-check is the largest op at
  0.232 s, the three scatters take 0.312 s and the four sort phases
  0.221 s (PERF.md §5).
- ``_classify_binsearch_core``: a pair of ``searchsorted`` joins — faster
  on CPU where binary search doesn't serialise. Bit-identical to the sort
  path: both compare full 160-bit oids (the sort path re-verifies its
  64-bit fold matches via a monotonic partner gather), as does the numpy
  reference below.

The monolithic route (:func:`classify_blocks`) never copies a block to pad
it: each column reaches the device as a body — a view of the caller's own
pages — and one padded tail (:func:`_split_columns`), joined on the device
by the ``*_split`` entries.

Classes: 0 = unchanged, 1 = insert, 2 = update, 3 = delete.
"""

import logging
import os

import numpy as np

from kart_tpu import telemetry as tm
from kart_tpu.ops._lazy import lazy_jit

L = logging.getLogger("kart_tpu.ops")

UNCHANGED = 0
INSERT = 1
UPDATE = 2
DELETE = 3


def _fold_oids(oids):
    """(n, 5) uint32 sha1 words -> (n,) int64 mixed fold. Object identity is
    already a content hash; folding 160 -> 64 bits keeps equality testing
    exact to within a 2^-64 per-pair collision (far below the sha1 trust
    the reference's own content addressing extends). The multiply/xor-shift
    mix stops structured oid differences from cancelling in the fold."""
    import jax.numpy as jnp

    a = oids.astype(jnp.uint64)
    h = a[:, 0] ^ (a[:, 1] << 32)
    h2 = a[:, 2] ^ (a[:, 3] << 32)
    h = (h * jnp.uint64(0x9E3779B97F4A7C15)) ^ h2
    h = h ^ (h >> 29)
    h = h * jnp.uint64(0xBF58476D1CE4E5B9)
    h = h ^ a[:, 4]
    return h.astype(jnp.int64)


def _classify_mergesort_core(
    old_keys, old_oids, new_keys, new_oids, old_count, new_count
):
    """Traceable core of the sort-based join (shared by the single-chip jit
    and the shard_map body). Padded inputs; counts are *dynamic* scalars so
    only the padded (bucket) shapes drive compilation.

    Keys are unique within each side (PKs / path hashes), so after a stable
    sort of concat(old, new) each key appears once or twice, old first —
    classification is a neighbour compare. Padding (PAD_KEY) sorts last and
    is masked out of the classes by the count mask at the end.

    The 160-bit oids travel through the sort as a 64-bit fold
    (:func:`_fold_oids`) — a third sort operand streams sequentially through
    the sort network, where gathering (n,5) oid rows by the sorted
    permutation afterwards is a large random HBM access pattern (measured
    ~3x slower end-to-end on TPU v5e at 10M rows).
    """
    import jax
    import jax.numpy as jnp

    n_old = old_keys.shape[0]
    n_new = new_keys.shape[0]
    total = n_old + n_new

    keys = jnp.concatenate([old_keys, new_keys])
    gidx = jnp.arange(total, dtype=jnp.int32)
    vals = jnp.concatenate([_fold_oids(old_oids), _fold_oids(new_oids)])
    # 2nd sort key = concat position: stable old-before-new on equal keys
    sk, sg, sv = jax.lax.sort((keys, gidx, vals), num_keys=2)
    is_old = sg < n_old

    pair = (sk[:-1] == sk[1:]) & is_old[:-1] & ~is_old[1:]
    pair_eq = pair & (sv[:-1] == sv[1:])
    false1 = jnp.zeros(1, dtype=bool)
    matched_left = jnp.concatenate([pair, false1])
    eq_left = jnp.concatenate([pair_eq, false1])
    matched_right = jnp.concatenate([false1, pair])
    eq_right = jnp.concatenate([false1, pair_eq])

    cls_sorted = jnp.where(
        is_old,
        jnp.where(matched_left, jnp.where(eq_left, UNCHANGED, UPDATE), DELETE),
        jnp.where(matched_right, jnp.where(eq_right, UNCHANGED, UPDATE), INSERT),
    ).astype(jnp.int8)
    out = jnp.zeros(total, jnp.int8).at[sg].set(cls_sorted)
    old_class = jnp.where(
        jnp.arange(n_old) < old_count, out[:n_old], UNCHANGED
    ).astype(jnp.int8)
    new_class = jnp.where(
        jnp.arange(n_new) < new_count, out[n_old:], UNCHANGED
    ).astype(jnp.int8)

    # partner row in `new` for each matched old row (0 when unmatched)
    partner_sorted = jnp.where(
        matched_left, jnp.roll(sg, -1) - n_old, 0
    ).astype(jnp.int32)
    partner_full = jnp.zeros(total, jnp.int32).at[sg].set(partner_sorted)
    idx_in_new = partner_full[:n_old]

    # Exactness restore: a pair the fold called equal is re-checked against
    # the full 160-bit oids. Both blocks are key-sorted so idx_in_new is
    # monotonic, unlike the random post-sort gather the fold exists to
    # avoid — yet as a row gather it is still the program's largest op on a
    # v5e (0.232 of 0.777 s at 10M rows, PERF.md §5). A fold collision
    # therefore surfaces as an UPDATE instead of a silent diff miss.
    full_eq = jnp.all(old_oids == new_oids[idx_in_new], axis=1)
    collide = (
        (old_class == UNCHANGED) & (jnp.arange(n_old) < old_count) & ~full_eq
    )
    old_class = jnp.where(collide, UPDATE, old_class).astype(jnp.int8)
    new_class = new_class.at[jnp.where(collide, idx_in_new, 0)].max(
        jnp.where(collide, UPDATE, 0).astype(jnp.int8)
    )

    counts = jnp.stack(
        [
            jnp.sum(new_class == INSERT),
            jnp.sum(old_class == UPDATE),
            jnp.sum(old_class == DELETE),
        ]
    )
    return old_class, new_class, idx_in_new, counts


_classify_padded = lazy_jit(_classify_mergesort_core)


def _classify_binsearch_core(
    old_keys, old_oids, new_keys, new_oids, old_count, new_count
):
    """Binary-search join: the CPU-backend variant."""
    import jax.numpy as jnp

    n_old = old_keys.shape[0]
    n_new = new_keys.shape[0]
    old_valid = jnp.arange(n_old) < old_count
    new_valid = jnp.arange(n_new) < new_count

    # old -> new join
    idx_in_new = jnp.searchsorted(new_keys, old_keys)
    idx_in_new_c = jnp.minimum(idx_in_new, n_new - 1)
    old_found = (new_keys[idx_in_new_c] == old_keys) & (idx_in_new < n_new)
    old_found &= idx_in_new_c < new_count
    oid_same = jnp.all(
        old_oids == new_oids[idx_in_new_c], axis=1
    )
    old_class = jnp.where(
        old_valid,
        jnp.where(
            old_found,
            jnp.where(oid_same, UNCHANGED, UPDATE),
            DELETE,
        ),
        UNCHANGED,
    ).astype(jnp.int8)

    # new -> old join (only inserts remain to be found)
    idx_in_old = jnp.searchsorted(old_keys, new_keys)
    idx_in_old_c = jnp.minimum(idx_in_old, n_old - 1)
    new_found = (old_keys[idx_in_old_c] == new_keys) & (idx_in_old < n_old)
    new_found &= idx_in_old_c < old_count
    new_class = jnp.where(
        new_valid,
        jnp.where(new_found, UNCHANGED, INSERT),
        UNCHANGED,
    ).astype(jnp.int8)
    # mark updates on the new side too (same classification, new-row view)
    new_oid_same = jnp.all(new_oids == old_oids[idx_in_old_c], axis=1)
    new_class = jnp.where(
        new_valid & new_found & ~new_oid_same, UPDATE, new_class
    ).astype(jnp.int8)

    counts = jnp.stack(
        [
            jnp.sum(new_class == INSERT),
            jnp.sum(old_class == UPDATE),
            jnp.sum(old_class == DELETE),
        ]
    )
    return old_class, new_class, idx_in_new_c, counts


_classify_padded_binsearch = lazy_jit(_classify_binsearch_core)


def _split_entry(core):
    """The jitted entry of the monolithic route: ``core`` with each of its
    four columns arriving as (body, tail) — the sidecar's own pages and one
    padded step of the bucket grid (:func:`_split_columns`) — joined on the
    device. Shapes depend on the bucket alone, so this compiles once per
    bucket as the six-argument core does. The program is named after the
    core (``jit__classify_mergesort_core_split``): the benchmark's kernel
    metrics find it by that prefix in the device trace."""

    def entry(
        old_keys, old_keys_tail, old_oids, old_oids_tail,
        new_keys, new_keys_tail, new_oids, new_oids_tail,
        old_count, new_count,
    ):
        import jax.numpy as jnp

        return core(
            jnp.concatenate([old_keys, old_keys_tail]),
            jnp.concatenate([old_oids, old_oids_tail]),
            jnp.concatenate([new_keys, new_keys_tail]),
            jnp.concatenate([new_oids, new_oids_tail]),
            old_count,
            new_count,
        )

    entry.__name__ = entry.__qualname__ = core.__name__ + "_split"
    return entry


_classify_split = lazy_jit(_split_entry(_classify_mergesort_core))
_classify_split_binsearch = lazy_jit(_split_entry(_classify_binsearch_core))


def _env_int(name, default):
    """Tolerant env knob: a malformed value must never kill the CLI."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        L.warning("ignoring malformed %s=%r", name, os.environ[name])
        return default


def note_device_fallback(what, e, to):
    """One device→host rung taken: the CLI still completes, but never
    silently — ``diff.device.fallbacks{what=…}`` counts it (a measurement
    asserts the counter stayed 0, or it would time the host engine under a
    device's name) and the log says which rung and why."""
    tm.incr("diff.device.fallbacks", what=what)
    L.warning(
        "device %s failed (%s: %s); using %s", what, type(e).__name__, e, to
    )


# below this row count the host engine beats the device round trip (and never
# touches backend init / compile — a `kart diff` of a small repo must be
# instant even when the accelerator is cold or its runtime is stuck). The
# value dates from round 2 (numpy 0.35 s vs device 1.85 s at 1M rows,
# transfer-dominated) and predates the native host engine; re-tuning it
# needs chip numbers for both engines in one cell (ROADMAP queue 1 #3).
DEVICE_MIN_ROWS = _env_int("KART_DEVICE_MIN_ROWS", 2_000_000)

# above this row count the accelerator path streams the blocks chunk-wise so
# host->HBM transfer of chunk i+1 overlaps the sort of chunk i (SURVEY §2.3
# "pipelined lazy diff streaming") instead of paying one monolithic upload
STREAM_MIN_ROWS = _env_int("KART_STREAM_MIN_ROWS", 16_000_000)
STREAM_CHUNK_ROWS = _env_int("KART_STREAM_CHUNK_ROWS", 8_000_000)


def device_profitable(n_rows):
    """Cost-model routing for the classify kernels: True when the device
    round trip is expected to beat the host engine.

    - Below DEVICE_MIN_ROWS the host path wins on any backend (no backend
      init, no compile, no transfer) — and the check runs before any jax
      import, so small diffs stay instant even with a wedged accelerator.
    - On an XLA-**CPU** backend the host engine wins at *every* size: the
      native C++ merge-join is sequential-scan bound (~1.1 s at 100M rows)
      where the XLA join lost 13.6x at 100M (measured r3: 65.3 s vs 4.8 s),
      and even the numpy twin beats XLA-CPU. XLA-CPU exists for correctness
      twins and virtual-mesh tests, not as a production diff engine.
    - On a real accelerator, size is the only question.

    KART_DIFF_DEVICE=1/0 forces the answer (tests, experiments)."""
    mode = os.environ.get("KART_DIFF_DEVICE", "auto")
    if mode == "0":
        return False
    if n_rows < DEVICE_MIN_ROWS and mode != "1":
        return False
    from kart_tpu.runtime import default_backend, jax_ready

    if not jax_ready():
        return False
    return mode == "1" or default_backend() != "cpu"


def classify_blocks(old_block, new_block):
    """FeatureBlock x2 -> (old_class np.int8 (n_old,), new_class (n_new,),
    counts dict). Host wrapper: unpads and returns numpy. Routing is a cost
    model (:func:`device_profitable`): the host engine owns small blocks,
    CPU backends and wedged accelerators; real accelerators get the sort-join
    kernel — streamed in double-buffered chunks at north-star scale so
    transfer overlaps compute. Bit-identical results on every route (the
    sort path device-verifies its oid fold against full oids)."""
    from kart_tpu.runtime import default_backend

    n_rows = max(old_block.count, new_block.count)
    if not device_profitable(n_rows):
        # the host merge-join reads count-sliced views directly — callers
        # may pass unpadded (mmap-backed) blocks with no copy at all
        return classify_blocks_host(old_block, new_block)
    try:
        if n_rows >= STREAM_MIN_ROWS and default_backend() != "cpu":
            return classify_blocks_streamed(old_block, new_block)
        import jax

        from kart_tpu.ops.blocks import bucket_size

        program, kernel = (
            ("binsearch", _classify_split_binsearch)
            if default_backend() == "cpu"
            else ("mergesort", _classify_split)
        )
        # the four stages are statements of the program, each under its own
        # span (docs/DEVICE.md §5). The two block_until_ready calls add no
        # wait: the kernel cannot start before its arguments have landed,
        # and np.asarray below would wait for the kernel anyway
        with tm.span(
            "diff.device.pack", rows=old_block.count + new_block.count
        ) as sp:
            host = _split_columns(old_block) + _split_columns(new_block)
            bucket = bucket_size(max(n_rows, 1))  # the larger side's
            # bytes the host copied: the tails it made; a view owns nothing
            sp.set(
                bucket=bucket,
                bytes=sum(a.nbytes for a in host if a.flags.owndata),
            )
        with tm.span("diff.device.transfer", bytes=sum(a.nbytes for a in host)):
            dev = jax.block_until_ready([jax.device_put(a) for a in host])
        with tm.span("diff.device.kernel", program=program, bucket=bucket):
            old_class, new_class, _, counts = jax.block_until_ready(
                kernel(*dev, old_block.count, new_block.count)
            )
    except Exception as e:
        # device OOM / runtime failure mid-call: the CLI must still complete
        # (north-star scale can exceed a single chip's HBM)
        note_device_fallback("device_classify", e, "host path")
        return classify_blocks_host(old_block, new_block)
    with tm.span("diff.device.fetch") as sp:
        old_class = np.asarray(old_class)
        new_class = np.asarray(new_class)
        counts = np.asarray(counts)
        sp.set(bytes=old_class.nbytes + new_class.nbytes + counts.nbytes)
        old_class = old_class[: old_block.count]
        new_class = new_class[: new_block.count]
    return (
        old_class,
        new_class,
        {"inserts": int(counts[0]), "updates": int(counts[1]), "deletes": int(counts[2])},
    )


def stream_chunk_splits(key_arrays, chunk_rows):
    """Key-space chunking for the streamed device paths: sorted key arrays
    (one per block side) -> (per-side split-point arrays, n_chunks), where
    chunk c of side s is rows ``splits[s][c]:splits[s][c+1]``. A key falls
    in the same chunk on every side, so merge-joins stay chunk-local.

    Boundaries balance the *combined* population: quantiles of one side
    alone collapse under key-range skew (e.g. a renumbered-PK revision
    whose new keys all exceed the old range would pile every new row into
    one chunk). Candidate keys are fine-grained quantiles of each side;
    each target combined-rank picks the nearest candidate."""
    chunk_rows = max(int(chunk_rows), 1)
    n_chunks = max(1, -(-max(len(k) for k in key_arrays) // chunk_rows))
    total = sum(len(k) for k in key_arrays)

    def _quantile_keys(keys, m):
        if not len(keys) or m <= 0:
            return keys[:0]
        return keys[(np.arange(1, m) * len(keys)) // m]

    cand = np.unique(
        np.concatenate([_quantile_keys(k, 4 * n_chunks) for k in key_arrays])
    )
    if len(cand):
        ranks = sum(np.searchsorted(k, cand) for k in key_arrays)
        targets = (np.arange(1, n_chunks) * total) // n_chunks
        picks = np.searchsorted(ranks, targets)
        bounds = np.unique(cand[np.minimum(picks, len(cand) - 1)])
    else:
        bounds = cand
    splits = tuple(
        np.concatenate(([0], np.searchsorted(k, bounds), [len(k)]))
        for k in key_arrays
    )
    return splits, len(bounds) + 1


def classify_blocks_streamed(old_block, new_block, chunk_rows=None):
    """Double-buffered chunked device classify for blocks too large to ship
    to HBM as one upload (SURVEY §2.3 "pipelined lazy diff streaming").

    Both blocks are key-sorted, so splitting the *key space* at common
    boundary values (quantiles of the larger side) partitions the merge-join
    into independent chunk-local joins: a key falls in the same chunk on both
    sides, and no old/new pair ever straddles a boundary. Each chunk is
    padded to one shared bucket size (a single compiled shape), transferred
    with ``jax.device_put`` — which is asynchronous — and dispatched
    immediately; with two chunks in flight, chunk i+1's host->HBM copy
    overlaps chunk i's on-device sort. Results drain back in order.

    Semantics identical to the monolithic kernel (tested); counts are the
    sum of per-chunk count vectors."""
    import jax

    from collections import deque

    from kart_tpu.ops.blocks import PAD_KEY, bucket_size as _bucket

    if chunk_rows is None:
        chunk_rows = max(STREAM_CHUNK_ROWS, 1)
    n_old, n_new = old_block.count, new_block.count
    old_keys = old_block.keys[:n_old]
    new_keys = new_block.keys[:n_new]
    (old_splits, new_splits), n_chunks = stream_chunk_splits(
        (old_keys, new_keys), chunk_rows
    )
    max_len = max(
        int(np.max(np.diff(old_splits))), int(np.max(np.diff(new_splits))), 1
    )
    bucket = _bucket(max_len)

    def _padded(keys, oids, lo, hi):
        k = np.full(bucket, PAD_KEY, dtype=np.int64)
        o = np.zeros((bucket, 5), dtype=np.uint32)
        k[: hi - lo] = keys[lo:hi]
        o[: hi - lo] = oids[lo:hi]
        return k, o

    old_class = np.empty(n_old, dtype=np.int8)
    new_class = np.empty(n_new, dtype=np.int8)
    totals = np.zeros(3, dtype=np.int64)
    in_flight = deque()

    def _drain():
        out, c, (olo, ohi), (nlo, nhi) = in_flight.popleft()
        oc, nc, _, counts = out
        with tm.span("diff.device.fetch", chunk=c) as sp:
            oc, nc, counts = np.asarray(oc), np.asarray(nc), np.asarray(counts)
            sp.set(bytes=oc.nbytes + nc.nbytes + counts.nbytes)
            old_class[olo:ohi] = oc[: ohi - olo]
            new_class[nlo:nhi] = nc[: nhi - nlo]
            totals[:] += counts

    # the monolithic path's four span names, per chunk. Nothing here waits
    # for the device but the fetch: transfer and kernel time what the host
    # spends enqueueing, and the overlap is read from the device trace
    for c in range(n_chunks):
        olo, ohi = int(old_splits[c]), int(old_splits[c + 1])
        nlo, nhi = int(new_splits[c]), int(new_splits[c + 1])
        with tm.span(
            "diff.device.pack", chunk=c, rows=ohi - olo + nhi - nlo, bucket=bucket
        ) as sp:
            ok, oo = _padded(old_keys, old_block.oids, olo, ohi)
            nk, no = _padded(new_keys, new_block.oids, nlo, nhi)
            nbytes = ok.nbytes + oo.nbytes + nk.nbytes + no.nbytes
            sp.set(bytes=nbytes)
        with tm.span("diff.device.transfer", chunk=c, bytes=nbytes):
            dev = [jax.device_put(a) for a in (ok, oo, nk, no)]
        with tm.span(
            "diff.device.kernel", chunk=c, program="mergesort", bucket=bucket
        ):
            out = _classify_padded(*dev, ohi - olo, nhi - nlo)
        in_flight.append((out, c, (olo, ohi), (nlo, nhi)))
        if len(in_flight) >= 2:
            _drain()
    while in_flight:
        _drain()
    return (
        old_class,
        new_class,
        {
            "inserts": int(totals[0]),
            "updates": int(totals[1]),
            "deletes": int(totals[2]),
        },
    )


def _split_columns(block):
    """(keys body, keys tail, oids body, oids tail): the block's two columns
    as the monolithic device kernels take them, copying at most one step of
    the bucket grid. The body is the first ``bucket_body(bucket)`` rows,
    which every block of that bucket has — a view of the caller's arrays
    (the sidecar's mmap'd pages, read-only and unaligned as they come). The
    tail is the rest of the bucket: a view too when the block arrives
    padded, else freshly made — the block's last rows, then ``PAD_KEY`` /
    zero oids."""
    from kart_tpu.ops.blocks import PAD_KEY, bucket_body, bucket_size

    n = block.count
    size = bucket_size(max(n, 1))
    body = bucket_body(size)
    keys, oids = block.keys, block.oids
    if len(keys) >= size:
        return keys[:body], keys[body:size], oids[:body], oids[body:size]
    keys_tail = np.full(size - body, PAD_KEY, dtype=np.int64)
    keys_tail[: n - body] = keys[body:n]
    oids_tail = np.zeros((size - body, 5), dtype=np.uint32)
    oids_tail[: n - body] = oids[body:n]
    return keys[:body], keys_tail, oids[:body], oids_tail


def classify_blocks_host(old_block, new_block):
    """Host-engine classify: the native C++ merge-join when the IO lib is
    built (sequential scans — 1.1s at 100M rows, where numpy's searchsorted
    pays a cache miss per probe), the numpy twin otherwise. Bit-identical
    to classify_blocks_reference either way (tested)."""
    from kart_tpu import native

    n_old, n_new = old_block.count, new_block.count
    res = native.classify_sorted(
        old_block.keys[:n_old],
        old_block.oids[:n_old].view(np.uint8).reshape(n_old, 20),
        new_block.keys[:n_new],
        new_block.oids[:n_new].view(np.uint8).reshape(n_new, 20),
    )
    if res is not None:
        return res
    old_class, new_class = classify_blocks_reference(old_block, new_block)
    return (
        old_class,
        new_class,
        {
            "inserts": int(np.sum(new_class == INSERT)),
            "updates": int(np.sum(old_class == UPDATE)),
            "deletes": int(np.sum(old_class == DELETE)),
        },
    )


def classify_blocks_reference(old_block, new_block):
    """Pure-numpy reference with identical semantics, for bit-compat tests."""
    old_keys = old_block.keys[: old_block.count]
    new_keys = new_block.keys[: new_block.count]
    old_oids = old_block.oids[: old_block.count]
    new_oids = new_block.oids[: new_block.count]

    idx = np.searchsorted(new_keys, old_keys)
    idxc = np.minimum(idx, max(len(new_keys) - 1, 0))
    if len(new_keys):
        found = (new_keys[idxc] == old_keys) & (idx < len(new_keys))
        oid_same = np.all(old_oids == new_oids[idxc], axis=1)
    else:
        found = np.zeros(len(old_keys), dtype=bool)
        oid_same = found
    old_class = np.where(
        found, np.where(oid_same, UNCHANGED, UPDATE), DELETE
    ).astype(np.int8)

    idx2 = np.searchsorted(old_keys, new_keys)
    idx2c = np.minimum(idx2, max(len(old_keys) - 1, 0))
    if len(old_keys):
        found2 = (old_keys[idx2c] == new_keys) & (idx2 < len(old_keys))
        oid_same2 = np.all(new_oids == old_oids[idx2c], axis=1)
    else:
        found2 = np.zeros(len(new_keys), dtype=bool)
        oid_same2 = found2
    new_class = np.where(
        found2, np.where(oid_same2, UNCHANGED, UPDATE), INSERT
    ).astype(np.int8)
    return old_class, new_class


def changed_indices(old_class, new_class):
    """-> (old_changed_idx, new_changed_idx): row indices whose values need
    materialising (everything except UNCHANGED). Host work the device
    never sees, so it has a span of its own inside ``diff.classify``."""
    with tm.span(
        "diff.changed_indices", rows=len(old_class) + len(new_class)
    ) as sp:
        old_idx = np.nonzero(old_class != UNCHANGED)[0]
        new_idx = np.nonzero(new_class != UNCHANGED)[0]
        sp.set(changed=len(old_idx) + len(new_idx))
    return old_idx, new_idx


def _columnar_equal_core(old_cols, new_cols, null_mask_old, null_mask_new):
    """Row equality over aligned columnar attribute data (the working-copy
    compare, reference hot loop #2 base.py:722): all columns equal and same
    null pattern. cols: (C, N) arrays (numeric/hash-encoded), masks (C, N)."""
    import jax.numpy as jnp

    return jnp.all((old_cols == new_cols) & (null_mask_old == null_mask_new), axis=0)


columnar_equal = lazy_jit(_columnar_equal_core)
