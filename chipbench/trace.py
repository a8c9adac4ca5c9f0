"""Reduce a profiler trace to the benchmark's device numbers.

`load(path)` reads a `.xplane.pb` written by `jax.profiler` into plain
events: per device, the operations that ran on it; on the host, the
spans of the thread that drove the run (the harness's own
`TraceAnnotation`s and Python frames).  `reduce(events, ...)` turns them
into:

- `window_s`: from the start of the first host span named `round` (or
  `run`) to the end of the last one;
- `busy_s`: per device, the union of the intervals in which an
  operation ran, inside the window; the mean over the devices is the
  device's busy time, `1 - busy / window` its idle share;
- `device_ops`: operations by total time, mean per device;
- `idle_gaps`: the window's idle time on each device, each gap named by
  the innermost host span that covers its midpoint (only spans whose
  name is a harness annotation or a frame of a file in `host_files`),
  summed by name, mean per device.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the trace line that carries one event per device operation
OPS_LINE = "XLA Ops"
WINDOW_SPANS = ("round", "run")

Event = Tuple[str, float, float]  # name, start ns, duration ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Dict:
    """{"devices": {plane name: [ops]}, "host": [spans]}, times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if any(n in WINDOW_SPANS for n, _, _ in evs):
                    host = evs
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _span_name(name: str, host_files: Optional[Sequence[str]]) -> Optional[str]:
    if not name.startswith("$"):
        return name  # an annotation
    if host_files is None:
        return None
    # a Python frame, "$file.py:line function"
    base = name[1:].split(":", 1)[0]
    return name[1:] if base in host_files else None


def _cover(spans: List[Tuple[str, float, float]], times: List[float]) -> List[str]:
    """For each instant (sorted), the innermost host span covering it.
    Spans of one thread nest, so a stack swept along time finds it."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else "outside")
    return out


def reduce(events: Dict, host_files: Optional[Sequence[str]] = None,
           top: int = 10) -> Dict:
    host = events["host"]
    marks = [(s, s + d) for n, s, d in host if n in WINDOW_SPANS]
    if not marks or not events["devices"]:
        raise ValueError("trace has no window span or no device operations")
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    spans = [(n2, s, s + d) for n, s, d in host
             if (n2 := _span_name(n, host_files)) is not None
             and s + d > lo and s < hi]
    n_dev = len(events["devices"])
    busy, op_time = [], defaultdict(float)
    gap_time = defaultdict(float)
    for ops in events["devices"].values():
        iv = _clip([(s, s + d) for _, s, d in ops], lo, hi)
        u = union(iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        for name, s, d in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                op_time[name] += (b - a) * 1e-9 / n_dev
        edges = [lo] + [t for ab in u for t in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        names = _cover(spans, [(a + b) / 2 for a, b in gaps])
        for name, (a, b) in zip(names, gaps):
            gap_time[name] += (b - a) * 1e-9 / n_dev
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / n_dev
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": busy,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": rank(op_time),
        "idle_gaps": rank(gap_time),
    }
