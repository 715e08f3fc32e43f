"""Device milliseconds an epoch spends in the tower: the traced window's own
time under `wdl.deep`, forward (`jvp(wdl.deep)`, and bare where a program
runs the tower outside a `grad`) and transposed
(`transpose(jvp(wdl.deep))`): the matmuls, their activations and the weight
gradients. Joined by `benchmarks/lib/scopes.py`; a program without
`scope_table` gives nothing."""

from benchmarks.lib import scopes


def read(ctx):
    return scopes.ms_per(ctx, scopes.epochs(ctx),
                         lambda scope, _event: scopes.bare(scope) == "wdl.deep")
