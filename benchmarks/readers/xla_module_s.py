"""Device seconds per traced operation of the programs whose name starts
``prefix``: durations on the ``XLA Modules`` line of the device planes."""

import reduce


def read(ctx, prefix):
    total = reduce.module_seconds(ctx["xla"], prefix)
    if total is None or not ctx["ops_walls"]:
        return None
    return total / len(ctx["ops_walls"])
