"""1 - busy / window over the traced rounds, busy being the union of the
intervals in which an operation ran on a device, mean over the cell's
devices (`trace.reduce`)."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr["idle_share"]
