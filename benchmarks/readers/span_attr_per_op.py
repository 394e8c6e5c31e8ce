"""A count the program took where the work happened, per traced operation:
the attribute ``attr`` of the spans ``span``, summed over all traced
operations, over their number, times ``scale`` (100 for a fraction read as
percent). None where no span carries the attribute (a program from before
it set it); 0.0 where they carry it at 0."""

import span_tree


def read(ctx, span, attr, scale=1.0):
    counted = [
        e["args"][attr] for events in ctx["ops_events"]
        for e in span_tree.complete(events, span) if attr in e.get("args", {})
    ]
    if not counted:
        return None
    return scale * sum(counted) / len(ctx["ops_events"])
