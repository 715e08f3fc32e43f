"""Device milliseconds an epoch spends laying the lookups' planes out: the
traced window's own time under `jvp(wdl.embed)` and
`transpose(jvp(wdl.embed))` in operations that are not one of the driver's
`program_lookups()` holders (`wdl_lookup_ms_per_epoch` has those): the copy
of each gathered plane for the concatenation, the concatenation, its
transpose's `split` and the copies that feed the scatter-adds. The two
readers together are the time under the two `wdl.embed` scopes. Joined by
`benchmarks/lib/scopes.py`; a program without `scope_table`, or a driver
that names no holders, gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    drv = ctx["driver"]
    if scopes.events(ctx) is None or not hasattr(drv, "program_lookups"):
        return None
    holders = drv.program_lookups()
    if not holders:
        return None
    return scopes.ms_per(
        ctx, scopes.epochs(ctx),
        lambda scope, event: scopes.bare(scope) == "wdl.embed"
        and scopes.NAME.match(event).group(1) not in holders)
