"""Operations and bytes a kernel's call needs, from its shapes, and the
least time the chip could take for them (the roofline's denominator)."""

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind):
    """The published peaks of ``device_kind``. A kind that is not in
    ``peaks.json`` is an error, never a default."""
    with open(PEAKS_FILE) as f:
        peaks = json.load(f)["device_kinds"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return peaks[device_kind]


def classify_sort_join_bytes(rows_old, rows_new):
    """One pass over the arrays of a ``_classify_mergesort_core`` call:
    every row's int64 key and 160-bit oid (five uint32) read once, one int8
    class written per row. The sort's own passes are not counted: they are
    the algorithm chosen, not what the answer needs."""
    return (rows_old + rows_new) * (8 + 5 * 4 + 1)


COSTS = {"classify_sort_join": classify_sort_join_bytes}


def least_seconds(cost, device_kind, **shapes):
    """Bytes of ``cost`` at ``shapes`` over the device's peak bytes/s. The
    kernels here do compares and moves, no matrix products, so the memory
    roof is the one that binds."""
    return COSTS[cost](**shapes) / peaks_for(device_kind)["hbm_bytes_per_s"]


def roofline_share(cost, device_kind, kernel_seconds, **shapes):
    """Percent of the roofline the kernel reached: least time / its time."""
    if kernel_seconds <= 0:
        raise ValueError("roofline share needs a kernel time above 0")
    return 100.0 * least_seconds(cost, device_kind, **shapes) / kernel_seconds
