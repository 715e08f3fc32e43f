"""Device milliseconds an epoch spends in the table lookups and their
transposes: the summed own time of the traced window's events that are a
`gather` or a `scatter`, or a fusion whose computation holds one, over the
epochs of the window's calls. A fusion's event does not say what it holds
(`%fusion.668 = ... fusion(...), calls=%fused_computation...`), so the names
come from the driver, which reads them off the executable the window ran
(`program_lookups`). What XLA makes of a lookup without either opcode (a
select over a table of a few rows) is not in it. With no trace, no such
driver, a program that hands out no executable (the driver then names
nothing: events told by their own opcode alone would be a part read as the
whole) or no such event, nothing is returned."""

import re

NAME = re.compile(r"%([\w.\-]+) = ")


def read(ctx):
    tr, drv = ctx["trace"], ctx["driver"]
    epochs = len(ctx["calls"]) * getattr(drv, "epochs", 0)
    if not tr or not epochs or not hasattr(drv, "program_lookups"):
        return None
    holders = drv.program_lookups()
    if not holders:
        return None
    found = [v for k, v in tr["op_seconds"].items()
             if NAME.match(k) and NAME.match(k).group(1) in holders]
    if not found:
        return None
    return 1e3 * sum(found) / epochs
