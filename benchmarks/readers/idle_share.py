"""Percent of the traced operations' host wall time in which no program ran
on the device."""

import reduce


def read(ctx):
    busy = reduce.device_busy_seconds(ctx["xla"])
    if busy is None or not ctx["ops_walls"]:
        return None
    return reduce.idle_share(busy, sum(ctx["ops_walls"]))
