"""Mosaic calls one level's histogram takes, by the program's own counters:
`tree.kernel.chunks{mode=fused}` over `train.trees`, both over the whole
process (a ratio, so the warm-up's trees do not skew it): the column
layout's chunks under the fused scan's 512-column cap, which is what every
level built at up to 32 nodes walks the rows that often for: 14 at 28 x 256
slots, 2 at 28 x 33. It gives the FUSED levels' count wherever a tree has a
fused level; a program whose levels are all in hist mode (a meshed grower)
gives `mode=hist`'s, the chunks under the wmax cap (7 at 28 x 256 slots).
Nothing on a program that does not count its chunks."""


def read(ctx):
    from shifu_tpu import obs

    counters = obs.registry().snapshot()["counters"]
    trees = counters.get("train.trees")
    if not trees:
        return None
    for mode in ("fused", "hist"):
        chunks = counters.get('tree.kernel.chunks{mode="%s"}' % mode)
        if chunks:
            return chunks / trees
    return None
