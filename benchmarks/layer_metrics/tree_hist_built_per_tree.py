"""Node histograms built a tree, by the program's own counters:
`tree.hist.built` over `train.trees`, both over the whole process (a ratio,
so the warm-up's trees do not skew it). At depth 6 with subtraction on at
levels 1 to 5 it is 1 + 1 + 2 + 4 + 8 + 16 = 32; 63 means subtraction fell
off."""


def read(ctx):
    from shifu_tpu import obs

    counters = obs.registry().snapshot()["counters"]
    trees = counters.get("train.trees")
    built = counters.get("tree.hist.built")
    if not trees or built is None:
        return None
    return built / trees
