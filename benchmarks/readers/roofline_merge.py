"""The merge classify's share of its roofline, in percent: the least time
the chip could take for every traced call (``cost`` in costs_merge.py, at
the census the ``census_span`` span reports: the three revisions' rows and
the keys of their union, over the device kind's peak) over the device time
of the programs whose name starts ``prefix``. None where no span carries the
census (a program from before it did) or no such program ran."""

import costs_merge
import reduce
import span_tree

CENSUS = ("rows_ancestor", "rows_ours", "rows_theirs", "union")


def read(ctx, prefix, cost, census_span):
    total = reduce.module_seconds(ctx["xla"], prefix)
    calls = [
        e["args"] for events in ctx["ops_events"]
        for e in span_tree.complete(events, census_span)
        if all(k in e.get("args", {}) for k in CENSUS)
    ]
    if total is None or not calls:
        return None
    least = sum(
        costs_merge.least_seconds(
            cost, ctx["device_kind"], **{k: call[k] for k in CENSUS}
        )
        for call in calls
    )
    return 100.0 * least / total
