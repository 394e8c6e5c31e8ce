"""Reference for ``-o feature-count``: the layer builder knows how many rows
it rewrote, and the command must name exactly that many."""

import re


def check(output, info):
    """-> {check name: bool} for the command's output bytes."""
    counts = re.findall(rb"(\d+) features? changed", output)
    return {
        "one_dataset_counted": len(counts) == 1,
        "count_equals_edits": [int(c) for c in counts] == [info["n_edits"]],
    }
