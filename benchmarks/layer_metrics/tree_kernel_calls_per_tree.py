"""Mosaic kernel calls a tree, by the program's own counters:
`tree.kernel.calls` over `train.trees`, both over the whole process (a ratio,
so the warm-up's trees do not skew it): the column layout's chunks times the
levels a tree builds. At HIGGS's 28 x 33 slots and depth 6 it is 2 x 6 = 12
under the fused scan and 1 x 6 = 6 in hist mode; 42 and 24 were the counts
while every feature took a 128-lane tile of its own. Nothing on a program
that does not count its kernel calls."""


def read(ctx):
    from shifu_tpu import obs

    counters = obs.registry().snapshot()["counters"]
    trees = counters.get("train.trees")
    calls = counters.get("tree.kernel.calls")
    if not trees or calls is None:
        return None
    return calls / trees
