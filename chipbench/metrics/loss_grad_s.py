"""Device seconds per round under the `loss_grad` scope, the forward and
backward passes of every loss gradient of both players, all agents
(`scopes.reduce` over the traced rounds, mean over the cell's devices);
none for a program whose round names no phase."""


def read(ctx):
    s = ctx["trace"] and ctx["trace"]["scopes"]
    return s["loss_grad_s"] if s and s["named_ops"] else None
