"""A statistic of the host wall times of the window's commands: ``mean``
(all the commands' time over all the commands), ``median``, or ``p<q>`` for
the nearest-rank q-th percentile. Taken over every command finished in the
window."""

import reduce


def read(ctx, stat):
    walls = ctx["ops_walls"]
    if not walls:
        return None
    if stat == "mean":
        return sum(walls) / len(walls)
    if stat == "median":
        return reduce.median(walls)
    return reduce.nearest_rank(walls, float(stat[1:]))
