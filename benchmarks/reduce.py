"""From samples, spans and the profiler's trace to numbers.

The yardstick's arithmetic, kept with the benchmark so that every PR
computes the same number in the same way: the statistics of a sample of
wall times, the reduction of program spans, and the reduction of a device
trace (``XLA Modules`` / ``XLA Ops`` lines on the ``/device:TPU:*`` planes)
to busy time, kernel time and the operations that took most of it.
"""

import glob
import math
import os
import statistics

DEVICE_PLANE = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


# -- statistics ---------------------------------------------------------------

def median(values):
    return float(statistics.median(values))


def nearest_rank(values, q):
    """The nearest-rank ``q``-th percentile (0 < q <= 100): the smallest
    sample with at least q% of the sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no samples")
    return float(ordered[max(1, math.ceil(q / 100.0 * len(ordered))) - 1])


# -- program spans ------------------------------------------------------------

def span_seconds(events, name):
    """Durations, in seconds, of the spans called ``name`` among Chrome
    trace events (``dur`` in microseconds), in order."""
    return [e["dur"] / 1e6 for e in events if e.get("name") == name and "dur" in e]


def span_attrs(events, name, attr):
    return [e.get("args", {}).get(attr) for e in events if e.get("name") == name]


def span_mean_per_op(ops_events, name):
    """Mean over operations of the seconds each spent in spans ``name``;
    None when no operation recorded one."""
    totals = [sum(span_seconds(events, name)) for events in ops_events]
    if not ops_events or not any(totals):
        return None
    return sum(totals) / len(ops_events)


# -- device trace -------------------------------------------------------------

def read_xplane(trace_dir):
    """The newest ``*.xplane.pb`` under ``trace_dir`` as a list of events
    ``{"plane", "line", "name", "start", "dur"}`` (seconds), device planes
    only. [] when the profiler wrote nothing."""
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        return []
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start": ev.start_ns / 1e9, "dur": ev.duration_ns / 1e9,
                })
    return events


def union_seconds(intervals):
    """Total length covered by (start, duration) intervals: overlapping
    ones count once."""
    total, end = 0.0, -math.inf
    for start, dur in sorted(intervals):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def _by_plane(events, line):
    planes = {}
    for e in events:
        if e["line"] == line:
            planes.setdefault(e["plane"], []).append(e)
    return planes


def device_busy_seconds(events):
    """Seconds in which a program ran on the device: the union of the
    ``XLA Modules`` intervals, averaged over the device planes. None when
    the trace has no such line."""
    planes = _by_plane(events, MODULES_LINE)
    if not planes:
        return None
    return sum(
        union_seconds([(e["start"], e["dur"]) for e in evs])
        for evs in planes.values()
    ) / len(planes)


def module_seconds(events, prefix):
    """Summed durations of the program runs whose name starts ``prefix``,
    averaged over the device planes; None when there is none."""
    planes = _by_plane(events, MODULES_LINE)
    sums = [
        sum(e["dur"] for e in evs if e["name"].startswith(prefix))
        for evs in planes.values()
    ]
    if not any(sums):
        return None
    return sum(sums) / len(sums)


def idle_share(busy_s, window_s):
    """Percent of ``window_s`` in which no program ran on the device."""
    if not window_s or window_s <= 0:
        raise ValueError("idle share needs a window longer than 0")
    return 100.0 * (1.0 - busy_s / window_s)


def short_op_name(name):
    """``%sort.16 = (u32[...]) sort(...)`` -> ``sort.16``: the HLO op's own
    name, without the instruction text."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def top_device_ops(events, limit=10):
    """[[short name, seconds]] of the ``XLA Ops`` that took most device
    time, summed over runs and averaged over the device planes."""
    planes = _by_plane(events, OPS_LINE)
    if not planes:
        return []
    totals = {}
    for evs in planes.values():
        for e in evs:
            key = short_op_name(e["name"])
            totals[key] = totals.get(key, 0.0) + e["dur"] / len(planes)
    return [
        [k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    ]


def host_side_seconds(ops_events, walls, busy_s, device_span, limit=10):
    """[[what the host was doing, seconds]]: where the commands' wall time
    went outside the device, longest first. Durations only, no clock
    alignment: each command's wall is split among its top-level spans on the
    thread that ran ``device_span``, the device's busy time is taken off
    ``device_span`` (the only span a program runs in), and what no span
    covers is the CLI around them."""
    totals, covered = {}, 0.0
    for events in ops_events:
        main = {e.get("tid") for e in events if e.get("name") == device_span}
        spans = sorted(
            (e for e in events if "dur" in e and e.get("tid") in main),
            key=lambda e: (e["ts"], -e["dur"]),
        )
        end = -math.inf
        for e in spans:
            if e["ts"] >= end:  # not inside the span before it
                totals[e["name"]] = totals.get(e["name"], 0.0) + e["dur"] / 1e6
                covered += e["dur"] / 1e6
                end = e["ts"] + e["dur"]
    if device_span in totals:
        totals[device_span + ":host_side"] = max(
            totals.pop(device_span) - (busy_s or 0.0), 0.0
        )
    totals["cli:outside_spans"] = max(sum(walls) - covered, 0.0)
    return [
        [k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    ]
