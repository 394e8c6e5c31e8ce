"""The error bar of the readers that lay the program's spans over the
device trace: over the traced operations, the most by which a program named
``module_prefix*`` sticks out of the ``anchor_span`` span that launched it
and waited for it, at the one clock offset that suits them all best
(span_tree.clock_offset). 0 when every program ran inside its span."""

import span_tree


def read(ctx, anchor_span, module_prefix):
    aligned = span_tree.clock_offset(
        ctx["ops_events"], ctx["xla"], anchor_span, module_prefix
    )
    return None if aligned is None else aligned[1]
