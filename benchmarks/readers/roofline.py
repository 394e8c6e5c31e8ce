"""A kernel's share of its roofline, in percent: the least time the chip
could take for the call (``cost`` in costs.py, at the row count the
``rows_span`` span reports for each side, over the device kind's peak) over
the device time of the programs whose name starts ``prefix``."""

import costs
import reduce


def read(ctx, prefix, cost, rows_span):
    total = reduce.module_seconds(ctx["xla"], prefix)
    rows = [
        r for events in ctx["ops_events"]
        for r in reduce.span_attrs(events, rows_span, "rows")
    ]
    if total is None or not rows:
        return None
    # every traced run of the kernel against the least time of every call
    least = sum(
        costs.least_seconds(cost, ctx["device_kind"], rows_old=r, rows_new=r)
        for r in rows
    )
    return 100.0 * least / total
