"""Device seconds per traced operation of the programs named ``prefix*`` on
the device plane that spent most in them: the slowest chip, where
xla_module_s gives the mean over chips."""

import device_planes


def read(ctx, prefix):
    planes = device_planes.module_seconds_by_plane(ctx["xla"], prefix)
    if not any(planes.values()) or not ctx["ops_walls"]:
        return None
    return max(planes.values()) / len(ctx["ops_walls"])
