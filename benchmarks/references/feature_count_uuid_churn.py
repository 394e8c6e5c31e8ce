"""Reference for ``-o feature-count`` over the republish commit of a text-pk
layer (the builder ``uuid_pk_churn_layer``): the command must name exactly
as many features as the builder inserted, updated and deleted; the dataset
must be laid out by the msgpack/hash path scheme (its ``path-structure.json``
as the builder read it back from the commit); and the hash-keyed count must
have run its cross-version guard — the program's span ``diff.hash_guard``,
read from the span aggregates the first command (spans on) left in this
process. A count that took the delta path instead has no such span."""

import re


def guard_ran():
    """Did a ``diff.hash_guard`` span close in this process?"""
    from kart_tpu import telemetry as tm

    return any(
        name == "diff.hash_guard" and hist["count"] > 0
        for name, _, hist in tm.snapshot()["histograms"]
    )


def check(output, info):
    commit = info["commits"]["churn"]
    edits = sum(
        len(commit[kind]) for kind in ("inserted_ids", "updated_ids", "deleted_ids")
    )
    counts = re.findall(rb"(\d+) features? changed", output)
    return {
        "one_dataset_counted": len(counts) == 1,
        "count_equals_edits": [int(c) for c in counts] == [edits],
        "path_structure_is_msgpack_hash": info["path_structure"].get("scheme")
        == "msgpack/hash",
        "hash_guard_ran": guard_ran(),
    }
