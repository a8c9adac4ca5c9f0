"""Seconds from process start to the first timed round: imports, the
device, weights and data from the seed, compilation (or loading it from
the persistent cache) and the rounds the correctness check reads."""


def read(ctx):
    return ctx["setup_s"]
