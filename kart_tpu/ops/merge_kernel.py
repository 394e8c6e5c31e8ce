"""Vectorized 3-way merge classification (reference: the libgit2 tree merge
behind `kart/merge.py:99-100` + per-feature conflict semantics of
`kart/merge_util.py`).

Kart gets per-feature merge "for free" because one feature == one blob at a
PK-determined path, and libgit2 merges trees path-by-path. Here the same
semantics run over whole key columns of the (ancestor, ours, theirs)
FeatureBlocks — no per-feature Python, no data-dependent control flow — in
three forms with one answer: on one device as two runs of the diff's
classify and the three-way rule over the keys they report changed
(:func:`merge_classify_two_diffs`); on the mesh as three searchsorted joins
over each shard's union key array (:func:`_merge_classify_padded_core`, the
shard body of ``parallel/sharded_merge.py``); on the host as the numpy twin
of those joins (:func:`_merge_classify_np`).

Per-key decision for versions a/o/t (absent = not present):
    o == t           -> KEEP_OURS   (same change both sides, incl. both absent)
    o == a           -> TAKE_THEIRS (only theirs changed)
    t == a           -> KEEP_OURS   (only ours changed)
    otherwise        -> CONFLICT

Codes: 0 = KEEP_OURS, 1 = TAKE_THEIRS, 2 = CONFLICT.

The router that picks between them is
:func:`kart_tpu.diff.backend.merge_classify`.
"""

import numpy as np

from kart_tpu import telemetry as tm

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


def _classify_side(side, ancestor_block, block, chunk_rows):
    """One of the merge's two diffs, ancestor -> ``side``, on the device
    route of ``kart diff`` itself: -> (ancestor classes, the side's
    classes). Its own ``diff.classify`` span, so the diff's children and
    census attributes (``diff.device.*``, ``input_bytes`` /
    ``resident_bytes``) say of a merge what they say of a diff."""
    from kart_tpu.ops.diff_kernel import classify_blocks_streamed

    rows = max(ancestor_block.count, block.count)
    with tm.span("diff.classify", side=side, backend="device_jax", rows=rows):
        if not rows:  # the dataset is in neither revision
            return np.zeros(0, dtype=np.int8), np.zeros(0, dtype=np.int8)
        return classify_blocks_streamed(ancestor_block, block, chunk_rows)[:2]


def _updated_rows(side_class, ancestor_rows, ancestor_class):
    """The rows of a side that rewrite the ancestor's rows ``ancestor_rows``
    (all ``UPDATE`` there): both list a key's update in key order, so the
    k-th updated row of the ancestor is the k-th updated row of the side."""
    from kart_tpu.ops.diff_kernel import UPDATE

    return np.flatnonzero(side_class == UPDATE)[
        np.cumsum(ancestor_class == UPDATE)[ancestor_rows] - 1
    ]


def decision_stats(decision):
    """The two counts every engine hands back beside its decisions."""
    return {
        "conflicts": int(np.count_nonzero(decision == CONFLICT)),
        "take_theirs": int(np.count_nonzero(decision == TAKE_THEIRS)),
    }


def merge_classify_two_diffs(
    ancestor_block, ours_block, theirs_block, chunk_rows=None
):
    """The one-device merge classify: ``kart diff``'s classify twice —
    ancestor -> ours, ancestor -> theirs, the chunked windowed join over
    resident pages (:func:`kart_tpu.ops.diff_kernel.classify_blocks_streamed`;
    the ancestor's pages are read by both, and are the pages a diff of
    either branch reads) — then the three-way rule on the host over the
    changed keys alone (span ``merge.combine``):

    * a key neither side changed keeps ours, one only theirs changed takes
      theirs, one only ours changed keeps ours;
    * a key both changed keeps ours where they left the same value (both
      deleted it, or both wrote the same oid) and is a conflict otherwise;
    * absence is a value: a key the ancestor lacks is an insert of one side
      (taken or kept) or of both (the same oid, or a conflict).

    -> ``merge_classify``'s contract, bit-identical to
    :func:`_merge_classify_np` over ``np.union1d`` of the three key columns
    (tested): (union (U,) int64, decision (U,) int8, presence (U,) int8,
    stats). Raises what the device raises. The union is the ancestor's keys
    with both sides' inserted keys merged in; nothing of its size is sorted
    or searched."""
    from kart_tpu.ops.diff_kernel import DELETE, INSERT, UNCHANGED, UPDATE

    a_keys = ancestor_block.keys[: ancestor_block.count]
    a_o, o_class = _classify_side("ours", ancestor_block, ours_block, chunk_rows)
    a_t, t_class = _classify_side("theirs", ancestor_block, theirs_block, chunk_rows)

    with tm.span("merge.combine") as span:
        # -- keys the ancestor holds: a class a side, per ancestor row
        changed_o, changed_t = a_o != UNCHANGED, a_t != UNCHANGED
        decision_a = np.where(changed_t & ~changed_o, TAKE_THEIRS, KEEP_OURS).astype(
            np.int8
        )
        both = np.flatnonzero(changed_o & changed_t)
        # both changed it: the same value only where both deleted it or both
        # wrote the same blob
        differ = a_o[both] != a_t[both]
        rewrote = np.flatnonzero((a_o[both] == UPDATE) & (a_t[both] == UPDATE))
        if len(rewrote):
            o_rows = _updated_rows(o_class, both[rewrote], a_o)
            t_rows = _updated_rows(t_class, both[rewrote], a_t)
            differ[rewrote] = np.any(
                ours_block.oids[o_rows] != theirs_block.oids[t_rows], axis=1
            )
        decision_a[both[differ]] = CONFLICT
        presence_a = (
            np.int8(7)
            - np.int8(2) * (a_o == DELETE).astype(np.int8)
            - np.int8(4) * (a_t == DELETE).astype(np.int8)
        )

        # -- keys it lacks: one side's inserts, or both sides'
        o_new = np.flatnonzero(o_class == INSERT)
        t_new = np.flatnonzero(t_class == INSERT)
        o_new_keys = ours_block.keys[o_new]
        t_new_keys = theirs_block.keys[t_new]
        new_keys = np.union1d(o_new_keys, t_new_keys).astype(np.int64)
        in_o = np.isin(new_keys, o_new_keys, assume_unique=True)
        in_t = np.isin(new_keys, t_new_keys, assume_unique=True)
        decision_new = np.where(in_t & ~in_o, TAKE_THEIRS, KEEP_OURS).astype(np.int8)
        shared = in_o & in_t
        if shared.any():
            decision_new[shared] = np.where(
                np.any(
                    ours_block.oids[o_new[in_t[in_o]]]
                    != theirs_block.oids[t_new[in_o[in_t]]],
                    axis=1,
                ),
                CONFLICT,
                KEEP_OURS,
            )
        presence_new = np.int8(2) * in_o.astype(np.int8) + np.int8(4) * in_t.astype(
            np.int8
        )

        # -- the union, in key order: the new keys slipped in among the old
        if len(new_keys):
            at = np.searchsorted(a_keys, new_keys)
            union = np.insert(a_keys, at, new_keys)
            decision = np.insert(decision_a, at, decision_new)
            presence = np.insert(presence_a, at, presence_new)
        else:
            union, decision, presence = np.asarray(a_keys), decision_a, presence_a
        span.set(
            changed_ours=int(np.count_nonzero(changed_o)) + len(o_new),
            changed_theirs=int(np.count_nonzero(changed_t)) + len(t_new),
            both=len(both) + int(np.count_nonzero(shared)),
        )
    return union, decision, presence, decision_stats(decision)


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
