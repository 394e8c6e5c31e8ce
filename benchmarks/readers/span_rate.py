"""A count the program took where the work happened, over the time it
took: the sum of the attribute ``attr`` of the spans ``span`` over the sum
of their seconds, times ``scale`` (1e-9 turns bytes per second into GB/s).
The count is the span's own, so the rate stays right when the count
changes."""

import span_tree


def read(ctx, span, attr, scale):
    spans = [e for events in ctx["ops_events"] for e in span_tree.complete(events, span)]
    counted = [e for e in spans if attr in e.get("args", {})]
    seconds = sum(e["dur"] for e in counted) / 1e6
    if not counted or seconds <= 0:
        return None
    return scale * sum(e["args"][attr] for e in counted) / seconds
