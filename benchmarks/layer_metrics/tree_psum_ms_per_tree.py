"""Device milliseconds a tree spends in all-reduce operations on the first
chip: the summed own time of the traced window's events whose HLO opcode is
`all-reduce` (or its `-start` / `-done` halves), whatever the instruction
is called (`jax.lax.psum` names it `%psum.<n>`), over the trees whose ends
fall inside the window: the tree program's seven and the two of the errors
program between trees. An all-reduce ends when the slowest chip has
arrived, so this holds the wait for it as well as the exchange. With no
such event (one chip, or no trace), nothing is returned."""

import re

# the opcode, not an operand that is called after one (`%all-reduce.5)`)
ALL_REDUCE = re.compile(r"\ball-reduce(-start|-done)?\(")


def read(ctx):
    tr = ctx["trace"]
    trees = len(ctx["driver"].unit_ends)
    if not tr or not trees:
        return None
    found = [v for k, v in tr["op_seconds"].items() if ALL_REDUCE.search(k)]
    if not found:
        return None
    return 1e3 * sum(found) / trees
