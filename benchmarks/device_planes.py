"""The device trace one plane at a time: what the readers of a program that
runs on several chips share. ``reduce.module_seconds`` and
``reduce.top_device_ops`` give the mean over the device planes; a roofline
over all the chips that ran a program, the slowest chip, and the seconds of
one kind of op need the planes apart."""

import reduce


def seconds_by_plane(xla, line, matches):
    """{plane: summed seconds} of the events on ``line`` whose name
    ``matches``; every device plane that has the line is a key, also at 0.0.
    {} without a trace."""
    planes = {}
    for e in xla:
        if e["line"] == line:
            planes.setdefault(e["plane"], 0.0)
            if matches(e["name"]):
                planes[e["plane"]] += e["dur"]
    return planes


def module_seconds_by_plane(xla, prefix):
    """Per device plane, the seconds of the program runs named ``prefix*``."""
    return seconds_by_plane(
        xla, reduce.MODULES_LINE, lambda name: name.startswith(prefix)
    )


def op_seconds_by_plane(xla, prefix):
    """Per device plane, the seconds of the ``XLA Ops`` whose short name
    (``%all-reduce.3 = ...`` -> ``all-reduce.3``) starts ``prefix``."""
    return seconds_by_plane(
        xla, reduce.OPS_LINE,
        lambda name: reduce.short_op_name(name).startswith(prefix),
    )
