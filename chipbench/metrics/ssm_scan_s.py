"""Device seconds per round in the Mamba-1 selective-scan kernels,
`ssm_scan_fwd` and `ssm_scan_bwd` (`kernels/ssm_scan.py`): each call's
own time in the traced rounds, mean over the cell's devices
(`scopes.reduce`'s `kernel_s`); none where neither kernel ran."""

KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")


def read(ctx):
    k = ctx["trace"]["scopes"]["kernel_s"] if ctx["trace"] else {}
    ran = [k[n] for n in KERNELS if n in k]
    return sum(ran) if ran else None
