"""The selective-scan kernels' share of their roofline, in %: the HBM
bytes a round's calls cannot do without (`kernel_bytes` of the
configuration's architecture module) at the chip's HBM bandwidth, over
their measured time (`ssm_scan_s`), by `peaks.share`.

This is the bandwidth side alone.  The kernels' arithmetic (an exp,
about five products and adds per state entry and step forward, some
twenty backward) runs on the vector unit, whose peak no cited source in
`peaks.py` gives; where the vector side bounds the kernels, their full
share is higher than this one, never lower."""
from chipbench import peaks, registry


def read(ctx):
    seconds = registry.metric("ssm_scan_s").read(ctx)
    if not seconds or ctx["peak"] is None:
        return None
    c = ctx["config"]
    moved = registry.arch(c["arch"]).kernel_bytes(c, ctx["traffic"])["ssm_scan"]
    return 100.0 * peaks.share(seconds, ctx["peak"], bytes=moved)
