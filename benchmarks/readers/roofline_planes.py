"""A program's share of its roofline where it runs on several chips at once,
in percent: the least time all the chips that ran it could take together for
the traced commands (``cost`` in costs.py at the row count the ``rows_span``
span reports for each side, over one chip's peak times the number of device
planes with a run of ``prefix*``) over the mean seconds those planes spent
in the program. The count is of the work the answer needs: the same as the
one-chip roofline's, whatever the batching."""

import costs
import device_planes
import reduce


def read(ctx, prefix, cost, rows_span):
    ran = [
        s for s in device_planes.module_seconds_by_plane(ctx["xla"], prefix).values()
        if s > 0
    ]
    rows = [
        r for events in ctx["ops_events"]
        for r in reduce.span_attrs(events, rows_span, "rows")
    ]
    if not ran or not rows:
        return None
    one_chip = sum(
        costs.least_seconds(cost, ctx["device_kind"], rows_old=r, rows_new=r)
        for r in rows
    )
    return 100.0 * (one_chip / len(ran)) / (sum(ran) / len(ran))
