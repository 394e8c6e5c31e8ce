"""Bytes the merge classify's answer needs, from the call's census, beside
``costs.py`` (which may not be edited for a new kernel): what the kernel's
share of its roofline is counted against."""

import costs


def merge_classify_bytes(rows_ancestor, rows_ours, rows_theirs, union):
    """One pass over what a three-way classify has to read and write: every
    row's int64 key and 160-bit oid (five uint32) of the three revisions
    read once, one decision byte written per key of their union. The same
    count whatever implements the kernel: two diffs that each read the
    ancestor earn nothing for reading it twice, and no pass of a sort or a
    search is counted — they are the algorithm chosen, not what the answer
    needs."""
    return (rows_ancestor + rows_ours + rows_theirs) * (8 + 5 * 4) + union


COSTS = {"merge_classify": merge_classify_bytes}


def least_seconds(cost, device_kind, **shapes):
    """Bytes of ``cost`` at ``shapes`` over the device's peak bytes/s
    (``peaks.json``, through ``costs.peaks_for``): compares and moves, no
    matrix product, so the memory roof is the one that binds."""
    return COSTS[cost](**shapes) / costs.peaks_for(device_kind)["hbm_bytes_per_s"]
