"""Seconds per round: the whole measured window over the rounds it
completed (host clock; every round ends in `block_until_ready`)."""


def read(ctx):
    return ctx["window_s"] / ctx["rounds"] if ctx["rounds"] else None
