"""Vectorized 3-way merge classification (reference: the libgit2 tree merge
behind `kart/merge.py:99-100` + per-feature conflict semantics of
`kart/merge_util.py`).

Kart gets per-feature merge "for free" because one feature == one blob at a
PK-determined path, and libgit2 merges trees path-by-path. Here the same
semantics run as one jitted kernel over the *union* key array of the
(ancestor, ours, theirs) FeatureBlocks: three searchsorted joins produce
per-key (present, oid) triples, then the classic 3-way rule classifies every
key at once — no per-feature Python, no data-dependent control flow.

Per-key decision for versions a/o/t (absent = not present):
    o == t           -> KEEP_OURS   (same change both sides, incl. both absent)
    o == a           -> TAKE_THEIRS (only theirs changed)
    t == a           -> KEEP_OURS   (only ours changed)
    otherwise        -> CONFLICT

Codes: 0 = KEEP_OURS, 1 = TAKE_THEIRS, 2 = CONFLICT.

This module holds the one-device kernels and their numpy twin; the router
that picks between them and the mesh (``parallel/sharded_merge.py``) is
:func:`kart_tpu.diff.backend.merge_classify`.
"""

import numpy as np

from kart_tpu.ops._lazy import lazy_jit
from kart_tpu.ops.blocks import PAD_KEY, bucket_size

KEEP_OURS = 0
TAKE_THEIRS = 1
CONFLICT = 2


def _join(version_keys, version_oids, version_count, union_keys):
    """For each union key: (present (bool), oid (5,) uint32 or 0)."""
    import jax.numpy as jnp

    n = version_keys.shape[0]
    idx = jnp.searchsorted(version_keys, union_keys)
    idxc = jnp.minimum(idx, n - 1)
    present = (version_keys[idxc] == union_keys) & (idx < n) & (idxc < version_count)
    oids = jnp.where(present[:, None], version_oids[idxc], 0)
    return present, oids


def _merge_classify_padded_core(
    a_keys, a_oids, a_count,
    o_keys, o_oids, o_count,
    t_keys, t_oids, t_count,
    union_keys, union_count,
):
    import jax.numpy as jnp

    union_valid = jnp.arange(union_keys.shape[0]) < union_count
    a_pres, a_oid = _join(a_keys, a_oids, a_count, union_keys)
    o_pres, o_oid = _join(o_keys, o_oids, o_count, union_keys)
    t_pres, t_oid = _join(t_keys, t_oids, t_count, union_keys)

    def same(p1, oid1, p2, oid2):
        both_absent = ~p1 & ~p2
        both_same = p1 & p2 & jnp.all(oid1 == oid2, axis=1)
        return both_absent | both_same

    o_eq_t = same(o_pres, o_oid, t_pres, t_oid)
    o_eq_a = same(o_pres, o_oid, a_pres, a_oid)
    t_eq_a = same(t_pres, t_oid, a_pres, a_oid)

    decision = jnp.where(
        o_eq_t,
        KEEP_OURS,
        jnp.where(
            o_eq_a,
            TAKE_THEIRS,
            jnp.where(t_eq_a, KEEP_OURS, CONFLICT),
        ),
    )
    decision = jnp.where(union_valid, decision, KEEP_OURS).astype(jnp.int8)
    n_conflicts = jnp.sum(decision == CONFLICT)
    n_take_theirs = jnp.sum(decision == TAKE_THEIRS)
    presence = (
        a_pres.astype(jnp.int8)
        + 2 * o_pres.astype(jnp.int8)
        + 4 * t_pres.astype(jnp.int8)
    )
    return decision, presence, n_conflicts, n_take_theirs


_merge_classify_padded = lazy_jit(_merge_classify_padded_core)


# from MERGE_STREAMED_MIN_ROWS rows the accelerator merge streams its three
# blocks chunk-wise (kart_tpu/diff/backend.py), so that the host->HBM
# transfer of chunk i+1 overlaps the joins of chunk i instead of one
# monolithic upload. The module's own choice from the size it observes, not a
# routing decision (kart_tpu/routing.py). The diff's device route is chunked
# at every size and has constants of its own (ops/diff_kernel.py)
MERGE_STREAMED_MIN_ROWS = 16_000_000
MERGE_CHUNK_ROWS = 8_000_000


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


def merge_classify_streamed(
    ancestor_block, ours_block, theirs_block, chunk_rows=None
):
    """Double-buffered chunked device merge classify — the merge analog of
    ``diff_kernel.classify_blocks_streamed`` (SURVEY §2.3 pipelined
    streaming): north-star-scale merges must not ship three whole blocks to
    HBM as one upload. Key-space chunks keep every 3-way decision
    chunk-local; per-chunk unions concatenate (in order) into the exact
    global sorted union, so output is identical to ``merge_classify``
    (tested). With two chunks in flight, chunk i+1's host->HBM copy
    overlaps chunk i's joins."""
    import jax

    from collections import deque

    if chunk_rows is None:
        chunk_rows = MERGE_CHUNK_ROWS
    blocks = (ancestor_block, ours_block, theirs_block)
    reals = tuple(
        (b.keys[: b.count], b.oids[: b.count]) for b in blocks
    )
    splits, n_chunks = stream_chunk_splits(
        tuple(keys for keys, _ in reals), chunk_rows
    )
    # per-chunk unions first: all chunks share one union bucket (one
    # compiled shape), and their ordered concatenation IS the global union
    unions = []
    for c in range(n_chunks):
        parts = [
            reals[s][0][splits[s][c] : splits[s][c + 1]] for s in range(3)
        ]
        unions.append(
            np.union1d(np.union1d(parts[0], parts[1]), parts[2]).astype(
                np.int64
            )
        )
    side_max = max(
        (
            int(np.max(np.diff(splits[s])))
            for s in range(3)
            if len(splits[s]) > 1
        ),
        default=1,
    )
    b_bucket = bucket_size(max(side_max, 1))
    u_bucket = bucket_size(max(max((len(u) for u in unions), default=1), 1))

    def _padded(keys, oids, lo, hi):
        k = np.full(b_bucket, PAD_KEY, dtype=np.int64)
        o = np.zeros((b_bucket, 5), dtype=np.uint32)
        k[: hi - lo] = keys[lo:hi]
        o[: hi - lo] = oids[lo:hi]
        return k, o

    out_decision = []
    out_presence = []
    totals = np.zeros(2, dtype=np.int64)
    in_flight = deque()

    def _drain():
        out, u_count = in_flight.popleft()
        decision, presence, n_conf, n_theirs = out
        out_decision.append(np.asarray(decision)[:u_count])
        out_presence.append(np.asarray(presence)[:u_count])
        totals[0] += int(n_conf)
        totals[1] += int(n_theirs)

    for c in range(n_chunks):
        args = []
        for s in range(3):
            lo, hi = int(splits[s][c]), int(splits[s][c + 1])
            k, o = _padded(reals[s][0], reals[s][1], lo, hi)
            args.extend((jax.device_put(k), jax.device_put(o), hi - lo))
        u = unions[c]
        u_padded = np.full(u_bucket, PAD_KEY, dtype=np.int64)
        u_padded[: len(u)] = u
        args.extend((jax.device_put(u_padded), len(u)))
        out = _merge_classify_padded(*args)
        in_flight.append((out, len(u)))
        if len(in_flight) >= 2:
            _drain()
    while in_flight:
        _drain()
    union = (
        np.concatenate(unions) if unions else np.zeros(0, dtype=np.int64)
    )
    decision = (
        np.concatenate(out_decision)
        if out_decision
        else np.zeros(0, dtype=np.int8)
    )
    presence = (
        np.concatenate(out_presence)
        if out_presence
        else np.zeros(0, dtype=np.int8)
    )
    return (
        union,
        decision,
        presence,
        {"conflicts": int(totals[0]), "take_theirs": int(totals[1])},
    )


def _join_np(block, union_keys):
    """Vectorized numpy twin of ``_join`` (unpadded)."""
    keys = block.keys[: block.count]
    oids = block.oids[: block.count]
    if not len(keys):
        return (
            np.zeros(len(union_keys), dtype=bool),
            np.zeros((len(union_keys), 5), dtype=np.uint32),
        )
    idx = np.searchsorted(keys, union_keys)
    idxc = np.minimum(idx, len(keys) - 1)
    present = (keys[idxc] == union_keys) & (idx < len(keys))
    out = np.where(present[:, None], oids[idxc], 0).astype(np.uint32)
    return present, out


def _merge_classify_np(ancestor_block, ours_block, theirs_block, union):
    """Vectorized numpy fallback with identical semantics to the jitted
    kernel (used when no jax backend is usable)."""
    a_pres, a_oid = _join_np(ancestor_block, union)
    o_pres, o_oid = _join_np(ours_block, union)
    t_pres, t_oid = _join_np(theirs_block, union)

    def same(p1, oid1, p2, oid2):
        return (~p1 & ~p2) | (p1 & p2 & np.all(oid1 == oid2, axis=1))

    o_eq_t = same(o_pres, o_oid, t_pres, t_oid)
    o_eq_a = same(o_pres, o_oid, a_pres, a_oid)
    t_eq_a = same(t_pres, t_oid, a_pres, a_oid)
    decision = np.where(
        o_eq_t,
        KEEP_OURS,
        np.where(o_eq_a, TAKE_THEIRS, np.where(t_eq_a, KEEP_OURS, CONFLICT)),
    ).astype(np.int8)
    presence = (
        a_pres.astype(np.int8)
        + 2 * o_pres.astype(np.int8)
        + 4 * t_pres.astype(np.int8)
    )
    return decision, presence


def merge_classify_reference(ancestor_block, ours_block, theirs_block):
    """Pure-numpy implementation of identical semantics (bit-compat tests)."""
    def index(block):
        return {
            int(k): bytes(block.oids[i].tobytes())
            for i, k in enumerate(block.keys[: block.count])
        }

    a, o, t = index(ancestor_block), index(ours_block), index(theirs_block)
    union = sorted(set(a) | set(o) | set(t))
    decisions = []
    for k in union:
        av, ov, tv = a.get(k), o.get(k), t.get(k)
        if ov == tv:
            decisions.append(KEEP_OURS)
        elif ov == av:
            decisions.append(TAKE_THEIRS)
        elif tv == av:
            decisions.append(KEEP_OURS)
        else:
            decisions.append(CONFLICT)
    return np.asarray(union, dtype=np.int64), np.asarray(decisions, dtype=np.int8)
