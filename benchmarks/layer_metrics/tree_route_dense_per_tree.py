"""Levels a tree routes its rows through without a per-row gather, by the
program's own counters: `tree.route.dense` over `train.trees`, both over the
whole process (a ratio, so the warm-up's trees do not skew it). At depth 6
it is 6 when every level takes the dense form; a program that does not count
its routing (the parent of PR 27) gives nothing."""


def read(ctx):
    from shifu_tpu import obs

    counters = obs.registry().snapshot()["counters"]
    trees = counters.get("train.trees")
    dense = counters.get("tree.route.dense")
    if not trees or dense is None:
        return None
    return dense / trees
