"""Mean seconds per traced operation spent in the program span ``span``."""

import reduce


def read(ctx, span):
    return reduce.span_mean_per_op(ctx["ops_events"], span)
