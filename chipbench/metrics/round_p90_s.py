"""The 90th percentile of the window's round times (host clock, each
round ending in `block_until_ready`), as `statistics.quantiles` cuts
them; needs ten rounds or more."""
import statistics


def read(ctx):
    times = ctx["times"]
    if len(times) < 10:
        return None
    return statistics.quantiles(times, n=10)[-1]
