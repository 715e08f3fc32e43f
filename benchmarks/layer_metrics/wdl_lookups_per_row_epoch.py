"""Table accesses a row-epoch as the program was compiled: the `gather` and
`scatter` instructions of the executable the window ran, each of which walks
every row once an epoch (`program_lookup_count` of the cell's driver, from
the text the program's dispatch seam keeps). 52 lookups a row and their 52
transposes are 45 + 52 on a v5e today (XLA makes selects of the 7 smallest
wide tables' gathers); it moves when a PR merges, removes or lowers a lookup
otherwise. A program that hands out no executable returns nothing."""


def read(ctx):
    drv = ctx["driver"]
    if not hasattr(drv, "program_lookup_count"):
        return None
    return drv.program_lookup_count()
