"""Share of the traced window in which no operation ran on a device, the
busy time averaged over the mesh's chips."""

from benchmarks.lib import xplane


def read(ctx):
    return xplane.idle_pct(ctx["trace"])
