"""Device seconds per round in the round's `local_steps` phase: busy time
given to the innermost operation, by the phase its instruction names
(`scopes.reduce` over the traced rounds, mean over the cell's devices);
none for a program whose round names no phase."""


def read(ctx):
    s = ctx["trace"] and ctx["trace"]["scopes"]
    return s["local_steps_s"] if s and s["named_ops"] else None
