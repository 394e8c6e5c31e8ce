"""Mean seconds per traced operation that the program span ``span`` spent
outside its child spans: its self time, by ``args.parent`` on its own
thread (span_tree.self_seconds)."""

import span_tree


def read(ctx, span):
    totals = [span_tree.self_seconds(events, span) for events in ctx["ops_events"]]
    found = [t for t in totals if t is not None]
    if not found:
        return None
    return sum(found) / len(ctx["ops_events"])
