"""How well the clock offset of the span readers is known: the width, in
seconds, of the interval of offsets that puts every program named
``module_prefix*`` inside the ``anchor_span`` span that launched it and
waited for it (paired in order, as span_tree.clock_offset pairs them, which
takes the interval's middle). Over clock pings on an idle device it is the
shortest launch latency plus the shortest completion notice. 0.0 when no
offset fits them all (align_residual then says by how much); None when the
pairs cannot be made."""

import span_tree


def read(ctx, anchor_span, module_prefix):
    spans = sorted(
        s for events in ctx["ops_events"]
        for s in span_tree.intervals(events, anchor_span)
    )
    runs = span_tree.modules(ctx["xla"], module_prefix)
    if not spans or len(spans) != len(runs):
        return None
    # span start + offset <= program start; program end <= span end + offset
    low = max(m + md - (s + sd) for (s, sd), (m, md) in zip(spans, runs))
    high = min(m - s for (s, _), (m, _) in zip(spans, runs))
    return max(high - low, 0.0)
