"""Reference for ``-o feature-count`` in a repository with a polygonal
spatial filter (the builder ``nodes_filtered_layer``): the builder knows the
pks it rewrote and the float64 point of each, the configuration holds the
filter polygon, and the command must name exactly as many features as there
are edited points inside the polygon — counted here by an even-odd ray cast
in float64, with nothing of the program under test imported. (The builder
keeps every edited point 1e-6 degrees clear of the polygon's edges, so a
point on an edge, which the filter counts as matching, does not occur.)"""

import json
import os
import re

import numpy as np

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "baseline4_nodes_10m_filtered.json",
)


def filter_ring():
    """The configuration's filter polygon: its one ring, closed, (n, 2)."""
    with open(CONFIG) as f:
        ring = json.load(f)["layer"]["params"]["filter"]["ring"]
    return np.asarray(ring, dtype=np.float64)


def points_in_ring(ring, x, y):
    """bool per point: inside the closed ring, by the even-odd rule. A ray
    towards +x from the point crosses the segment a -> b when the segment
    straddles the point's y and meets that line right of the point."""
    inside = np.zeros(len(x), dtype=bool)
    for (ax, ay), (bx, by) in zip(ring[:-1], ring[1:]):
        if ay == by:
            continue  # a horizontal segment straddles no y
        straddles = (ay > y) != (by > y)
        at_x = ax + (y - ay) * (bx - ax) / (by - ay)
        inside ^= straddles & (x < at_x)
    return inside


def edits_in_polygon(info):
    xy = np.asarray(info["edit_xy"], dtype=np.float64)
    return int(np.count_nonzero(points_in_ring(filter_ring(), xy[:, 0], xy[:, 1])))


def check(output, info):
    """-> {check name: bool} for the command's output bytes."""
    counts = re.findall(rb"(\d+) features? changed", output)
    return {
        "one_dataset_counted": len(counts) == 1,
        "count_equals_edits_in_polygon": [int(c) for c in counts]
        == [edits_in_polygon(info)],
    }
