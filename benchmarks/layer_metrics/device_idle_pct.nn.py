"""Share of the traced window in which no operation ran on the device."""

from benchmarks.lib import xplane


def read(ctx):
    return xplane.idle_pct(ctx["trace"])
