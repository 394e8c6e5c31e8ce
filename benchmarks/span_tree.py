"""The program's spans as a tree, and on the device trace's clock.

What the span readers share. A span event names the span that caused it
(``args.parent``: the one open below it on its thread), every command's
spans descend from one root (``cli.command``) on its main thread, and each
exported file carries ``kart_trace_epoch.args.unix``: the unix time of the
events' ``ts = 0``.

The device trace is on another clock: the profiler counts nanoseconds from
the start of its session, and the unix time of that start is kept on the
``Task Environment`` plane, which ``reduce.read_xplane`` (device planes
only) does not hand on. So the offset between the two clocks is taken from
what must hold between them: a program runs inside the span that launched
it and waited for it. Over all traced commands that leaves an interval of
possible offsets as narrow as the shortest launch and the shortest
completion notice; its middle is the offset used, and by how much the
interval fails to exist is the residual (:func:`clock_offset`).
"""

import reduce

ROOT_SPAN = "cli.command"
EPOCH_EVENT = "kart_trace_epoch"


def complete(events, name=None):
    """The finished spans among Chrome trace events, all or those called
    ``name``."""
    return [
        e for e in events
        if e.get("ph") == "X" and "dur" in e and name in (None, e.get("name"))
    ]


def epoch_unix(events):
    """Unix seconds of the file's ``ts = 0``; 0.0 where it names none (the
    events then stay on the program's own clock)."""
    for e in events:
        if e.get("name") == EPOCH_EVENT:
            return float(e.get("args", {}).get("unix", 0.0))
    return 0.0


def intervals(events, name, main_only=False):
    """[(start, seconds)] of the spans ``name`` of one command, in unix
    seconds; ``main_only`` keeps those on the thread of the root span."""
    epoch = epoch_unix(events)
    spans = complete(events, name)
    main = {e.get("tid") for e in complete(events, ROOT_SPAN)} if main_only else ()
    if main:
        spans = [e for e in spans if e.get("tid") in main]
    return [(epoch + e["ts"] / 1e6, e["dur"] / 1e6) for e in spans]


def self_seconds(events, name):
    """Seconds the spans ``name`` of one command spent outside their child
    spans: each one's duration less what the events on its thread that name
    it as ``parent`` cover. None where no span is called ``name`` or the
    record names no parents at all (a program from before it did)."""
    spans = complete(events)
    if not any("parent" in e.get("args", {}) for e in spans):
        return None
    total = None
    for p in spans:
        if p["name"] != name:
            continue
        children = [
            (c["ts"] / 1e6, c["dur"] / 1e6) for c in spans
            if c.get("args", {}).get("parent") == name
            and c.get("tid") == p.get("tid")
            and p["ts"] <= c["ts"] < p["ts"] + p["dur"]
        ]
        total = (total or 0.0) + p["dur"] / 1e6 - reduce.union_seconds(children)
    return total


def modules(xla, prefix=""):
    """[(start, seconds)] of the ``XLA Modules`` events whose name starts
    ``prefix``, on the first device plane, in order; [] without a trace."""
    runs = [e for e in xla if e["line"] == reduce.MODULES_LINE]
    first = min((e["plane"] for e in runs), default=None)
    return sorted(
        (e["start"], e["dur"]) for e in runs
        if e["plane"] == first and e["name"].startswith(prefix)
    )


def clock_offset(ops_events, xla, span, prefix):
    """-> (offset, residual) in seconds: ``offset`` added to a unix time of
    the program gives the time on the device trace's clock. Taken from the
    traced commands' ``span`` events and the programs named ``prefix*``
    they launched and waited for, paired in order: each program has to lie
    inside its span. ``residual`` is the most by which one still sticks out
    at the offset chosen; 0.0 when one offset fits every pair. None when
    the pairs cannot be made (no such span, or another number of programs)."""
    spans = sorted(s for events in ops_events for s in intervals(events, span))
    runs = modules(xla, prefix)
    if not spans or len(spans) != len(runs):
        return None
    # span start + offset <= program start; program end <= span end + offset
    low = max(m + md - (s + sd) for (s, sd), (m, md) in zip(spans, runs))
    high = min(m - s for (s, _), (m, _) in zip(spans, runs))
    return (low + high) / 2.0, max((low - high) / 2.0, 0.0)


def uncovered_seconds(span, busy):
    """Seconds of the interval ``span`` (start, seconds) that none of the
    ``busy`` intervals covers."""
    start, dur = span
    clipped = [
        (max(b, start), min(b + bd, start + dur) - max(b, start))
        for b, bd in busy if b < start + dur and b + bd > start
    ]
    return dur - reduce.union_seconds(clipped)
