"""The whole round's share of the chips' bf16 peak: model FLOPs per
round (`flops.round_flops`, counted from shapes) over round time x chips
x peak.  Round time is the traced run's measured window, on the host
clock."""


def read(ctx):
    if ctx["peak"] is None or not ctx["rounds"]:
        return None
    round_s = ctx["window_s"] / ctx["rounds"]
    return ctx["flops_per_round"] / (round_s * ctx["chips"]
                                     * ctx["peak"]["bf16_flops_per_s"])
