"""Device milliseconds an epoch spends in the network's backward pass: the
traced window's own time under `nn.bwd` that is not the forward pass
`nn_fwd_ms_per_epoch` reads: `nn.bwd/transpose(jvp(nn.fwd))`, the weight and
input gradients, and what else the scope holds (the parameter vector cut
into matrices and the gradients laid back into one, the loss's own
derivative). Joined by `benchmarks/lib/scopes.py`; a program without
`scope_table` gives nothing."""

from benchmarks.lib import scopes, spec

FORWARD = spec.load_module("layer_metrics", "nn_fwd_ms_per_epoch").FORWARD


def read(ctx):
    return scopes.ms_per(
        ctx, scopes.epochs(ctx),
        lambda scope, _event: scope.split("/")[0] == "nn.bwd"
        and scope not in FORWARD)
