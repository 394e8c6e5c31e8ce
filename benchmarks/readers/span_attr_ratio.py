"""A share the program counted where the work happened: the sum of the
attribute ``numerator`` over the sum of the attribute ``denominator`` of
the spans ``span``, all traced operations together, times ``scale`` (100
for percent). Only spans that carry both attributes count; None where none
does (a program from before it set them), or the denominators sum to 0."""

import span_tree


def read(ctx, span, numerator, denominator, scale):
    counted = [
        e["args"] for events in ctx["ops_events"]
        for e in span_tree.complete(events, span)
        if numerator in e.get("args", {}) and denominator in e.get("args", {})
    ]
    total = sum(a[denominator] for a in counted)
    if not counted or total <= 0:
        return None
    return scale * sum(a[numerator] for a in counted) / total
