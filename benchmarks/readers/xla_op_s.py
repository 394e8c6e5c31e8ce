"""Device seconds per traced operation of the ``XLA Ops`` whose short name
starts ``prefix`` (``all-reduce``: every collective the psum became), mean
over the device planes."""

import device_planes


def read(ctx, prefix):
    planes = device_planes.op_seconds_by_plane(ctx["xla"], prefix)
    if not any(planes.values()) or not ctx["ops_walls"]:
        return None
    return sum(planes.values()) / len(planes) / len(ctx["ops_walls"])
