"""Reference for ``-o feature-count`` over a republish commit (the builder
``int_pk_churn_layer``): the builder knows the three edit sets of each of
its commits — inserted, updated and deleted pks — and the command must name
exactly as many features as the commit it diffs holds in the three
together. ``check`` is the ``churn`` commit's and adds ``no_join_overflows``:
uniform churn must never send the one-chip classify to the sort-join, which
is read from the program's own counter ``diff.device.join_overflows`` after
the command that has just run (0 too where the host engine answered, as in
a rehearsal). ``check_commit`` is what the ``bulk`` commit's reference
shares."""

import re


def join_overflows():
    """Calls the windowed join has handed to the sort-join in this process."""
    from kart_tpu import telemetry as tm

    return sum(
        v for (name, _), v in tm.counters_snapshot().items()
        if name == "diff.device.join_overflows"
    )


def check_commit(output, info, branch):
    """-> {check name: bool} for the bytes of ``kart diff HEAD...<branch>
    -o feature-count``."""
    commit = info["commits"][branch]
    edits = sum(
        len(commit[kind]) for kind in ("inserted_pks", "updated_pks", "deleted_pks")
    )
    counts = re.findall(rb"(\d+) features? changed", output)
    return {
        "one_dataset_counted": len(counts) == 1,
        "count_equals_edits": [int(c) for c in counts] == [edits],
    }


def check(output, info):
    return {
        **check_commit(output, info, "churn"),
        "no_join_overflows": join_overflows() == 0,
    }
