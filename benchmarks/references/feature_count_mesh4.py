"""Reference for ``-o feature-count`` on the four-chip mesh: the layer
builder knows how many rows it rewrote, and the command must name exactly
that many (feature_count.py's two checks, copied); and the classify of the
command that has just run has to have gone over four shards. That is read
from the program's own gauge, ``diff.device.shards``, which
``classify_blocks_batched`` sets from the mesh it ran on — so it says
nothing of how the count was computed, and a host-engine answer, which sets
no gauge, fails it."""

import re

SHARDS = 4


def shards_of_last_classify():
    """The ``diff.device.shards`` gauge; None when no mesh classify ran."""
    from kart_tpu import telemetry as tm

    for name, _, value in tm.snapshot()["gauges"]:
        if name == "diff.device.shards":
            return value
    return None


def check(output, info):
    """-> {check name: bool} for the command's output bytes."""
    counts = re.findall(rb"(\d+) features? changed", output)
    return {
        "one_dataset_counted": len(counts) == 1,
        "count_equals_edits": [int(c) for c in counts] == [info["n_edits"]],
        "shards_is_4": shards_of_last_classify() == SHARDS,
    }
