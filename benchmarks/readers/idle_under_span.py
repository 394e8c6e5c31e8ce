"""Seconds per traced operation in which the program span ``span`` was
open on the command's main thread and no program ran on the device: the
span's intervals, put on the device trace's clock, less what the ``XLA
Modules`` intervals cover of them. The clocks are laid over each other by
span_tree.clock_offset, from ``anchor_span`` and the programs named
``module_prefix*`` that ran under it."""

import span_tree


def read(ctx, span, anchor_span, module_prefix):
    aligned = span_tree.clock_offset(
        ctx["ops_events"], ctx["xla"], anchor_span, module_prefix
    )
    if aligned is None:
        return None
    offset, _ = aligned
    busy = span_tree.modules(ctx["xla"])
    spans = [
        (start + offset, dur) for events in ctx["ops_events"]
        for start, dur in span_tree.intervals(events, span, main_only=True)
    ]
    if not spans:
        return None
    idle = sum(span_tree.uncovered_seconds(s, busy) for s in spans)
    return idle / len(ctx["ops_events"])
