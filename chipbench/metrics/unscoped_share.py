"""The share of the device's busy time whose innermost operation names
no phase of the round (`scopes.reduce` over the traced rounds): what
the per-phase seconds leave unexplained; none for a program whose round
names no phase."""


def read(ctx):
    s = ctx["trace"] and ctx["trace"]["scopes"]
    return s["unscoped_share"] if s and s["named_ops"] else None
