"""Vectorized 3-way merge classification (reference: the libgit2 tree merge
behind `kart/merge.py:99-100` + per-feature conflict semantics of
`kart/merge_util.py`).

Kart gets per-feature merge "for free" because one feature == one blob at a
PK-determined path, and libgit2 merges trees path-by-path. Here the same
semantics run over whole key columns of the (ancestor, ours, theirs)
FeatureBlocks — no per-feature Python, no data-dependent control flow: a
merge is two diffs, ancestor -> ours and ancestor -> theirs, and the
three-way rule over the keys they report changed
(:func:`merge_classify_two_diffs`). The diffs are whatever classify the
caller hands in; :func:`kart_tpu.diff.backend.merge_classify` hands in the
diff backend the routing ladder picks, so a merge runs on the host, one
device or the mesh exactly as ``kart diff`` does.

Per-key decision for versions a/o/t (absent = not present):
    o == t           -> KEEP_OURS   (same change both sides, incl. both absent)
    o == a           -> TAKE_THEIRS (only theirs changed)
    t == a           -> KEEP_OURS   (only ours changed)
    otherwise        -> CONFLICT

Codes: 0 = KEEP_OURS, 1 = TAKE_THEIRS, 2 = CONFLICT.
"""

import numpy as np

from kart_tpu import telemetry as tm

KEEP_OURS = 0
TAKE_THEIRS = 1
CONFLICT = 2


def _updated_rows(side_class, ancestor_rows, ancestor_class):
    """The rows of a side that rewrite the ancestor's rows ``ancestor_rows``
    (all ``UPDATE`` there): both list a key's update in key order, so the
    k-th updated row of the ancestor is the k-th updated row of the side."""
    from kart_tpu.ops.diff_kernel import UPDATE

    return np.flatnonzero(side_class == UPDATE)[
        np.cumsum(ancestor_class == UPDATE)[ancestor_rows] - 1
    ]


def merge_classify_two_diffs(ancestor_block, ours_block, theirs_block, classify):
    """The merge classify: ``classify(side, old_block, new_block) ->
    (old_class, new_class)`` twice — ``side`` ``"ours"`` then ``"theirs"``,
    each from the ancestor (on one device the ancestor's resident pages are
    read by both, and are the pages a diff of either branch reads) — then
    the three-way rule on the host over the changed keys alone (span
    ``merge.combine``):

    * a key neither side changed keeps ours, one only theirs changed takes
      theirs, one only ours changed keeps ours;
    * a key both changed keeps ours where they left the same value (both
      deleted it, or both wrote the same oid) and is a conflict otherwise;
    * absence is a value: a key the ancestor lacks is an insert of one side
      (taken or kept) or of both (the same oid, or a conflict).

    -> ``merge_classify``'s contract, bit-identical to
    :func:`merge_classify_reference` (tested): (union (U,) int64, decision
    (U,) int8, presence (U,) int8, stats). Raises what ``classify`` raises.
    The union is the ancestor's keys with both sides' inserted keys merged
    in; nothing of its size is sorted or searched."""
    from kart_tpu.ops.diff_kernel import DELETE, INSERT, UNCHANGED, UPDATE

    a_keys = ancestor_block.keys[: ancestor_block.count]
    a_o, o_class = classify("ours", ancestor_block, ours_block)
    a_t, t_class = classify("theirs", ancestor_block, theirs_block)

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
    return union, decision, presence, {
        "conflicts": int(np.count_nonzero(decision == CONFLICT)),
        "take_theirs": int(np.count_nonzero(decision == TAKE_THEIRS)),
    }


def merge_classify_reference(ancestor_block, ours_block, theirs_block):
    """The dict-per-key oracle of identical semantics (bit-compat tests) ->
    (union (U,) int64, decision (U,) int8, presence (U,) int8)."""
    def index(block):
        return {
            int(k): bytes(block.oids[i].tobytes())
            for i, k in enumerate(block.keys[: block.count])
        }

    a, o, t = index(ancestor_block), index(ours_block), index(theirs_block)
    union = sorted(set(a) | set(o) | set(t))
    decisions, presence = [], []
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
        presence.append((k in a) + 2 * (k in o) + 4 * (k in t))
    return (
        np.asarray(union, dtype=np.int64),
        np.asarray(decisions, dtype=np.int8),
        np.asarray(presence, dtype=np.int8),
    )
