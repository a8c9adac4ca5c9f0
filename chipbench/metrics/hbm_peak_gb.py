"""Highest device memory held during the run, over the cell's devices,
in GB (1e9 bytes): live arrays plus program scratch, as the device's
allocator reports them (`harness.memory_peak_bytes`)."""


def read(ctx):
    peaks = [p for p in ctx["memory_peak"] if p is not None]
    return max(peaks) / 1e9 if peaks else None
