"""Device milliseconds an epoch spends in the network's forward pass: the
traced window's own time under `nn.bwd/jvp(nn.fwd)` (the forward pass as
`value_and_grad` runs it, which keeps each layer's activations) and under a
bare `nn.fwd`, where a program has one. The validation error's forward pass
is `nn.valid`'s. Joined by `benchmarks/lib/scopes.py`; a program without
`scope_table` gives nothing."""

from benchmarks.lib import scopes

FORWARD = ("nn.bwd/jvp(nn.fwd)", "nn.fwd")


def read(ctx):
    return scopes.ms_per(ctx, scopes.epochs(ctx),
                         lambda scope, _event: scope in FORWARD)
