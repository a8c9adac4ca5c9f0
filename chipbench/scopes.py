"""Split the device's busy time by the scopes the program names.

`core/engine.py make_phases` runs each of the round's four phases under
`jax.named_scope(<phase>)` and each loss-gradient call under
`jax.named_scope("loss_grad")`; the model and its kernels may name
scopes of their own (`kernels/ssm_scan.py`: `ssm_scan`).  The names
reach the compiled module as
`metadata={op_name="jit(round)/<phase>/loss_grad/..."}`.

- `op_scopes(hlo_text)` maps each instruction of the compiled module
  (`Compiled.as_text()`) to `(phase, names)`: the first phase that its
  `op_name` path names (a merged op lists several paths, `;` apart) and
  every scope name on that path, read for a fusion from the op it
  returns; where that names no phase, where the op's own metadata, its
  users or operands, or its loop say (see `op_scopes`); else
  `(None, frozenset())`.
- `reduce(events, op_scopes)` takes the events of `trace.load` and, over
  the window of `trace.reduce`, gives each instant of a device's busy
  time to the innermost operation running then (the one that started
  last; on a tie the shorter), and so to that operation's phase, or to
  "unscoped" when the operation is not in the map or names no phase,
  and to every scope name on its path (`scope_s`).  The four phases and
  "unscoped" sum to the device's busy union.  Apart from that split,
  `kernel_s` sums each operation's own time by its instruction's base
  name.  The seconds are the mean over devices, per `round` host span.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from typing import Dict, FrozenSet, List, Optional, Tuple

from .trace import WINDOW_SPANS

PHASES = ("broadcast", "exchange_corrections", "local_steps", "aggregate")
LOSS_GRAD = "loss_grad"
#: the keys of `reduce`'s seconds per round, each a per-layer metric
METRICS = tuple(f"{p}_s" for p in PHASES) + ("loss_grad_s", "unscoped_s")

Scope = Tuple[Optional[str], FrozenSet[str]]
NO_SCOPE: Scope = (None, frozenset())

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_OP = re.compile(r"^%?([^\s=]+)")
_TOKEN = re.compile(r"[\w.\-]+")
_JIT = re.compile(r"^jit\(.*\)$")
_SUFFIX = re.compile(r"\.\d+$")


def path_scope(op_name: str) -> Optional[Scope]:
    """(phase, names) of an `op_name`: the first of its `;`-separated
    paths that names a phase, or None.  `names` are the tokens of the
    path's components but the last, the primitive, which names nothing,
    and the first where it is the program's `jit(...)` wrapper."""
    for path in op_name.split(";"):
        comps = path.split("/")[:-1]
        if comps and _JIT.match(comps[0]):
            comps = comps[1:]
        names = [t for c in comps for t in _TOKEN.findall(c)]
        phase = next((t for t in names if t in PHASES), None)
        if phase is not None:
            return phase, frozenset(names)
    return None


def instructions(hlo_text: str):
    """(computation, name, is_root, rest of the line) of every
    instruction of an HLO module's text."""
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            yield comp, m.group(2), bool(m.group(1)), m.group(3)
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)


def op_scopes(hlo_text: str) -> Dict[str, Scope]:
    """Each instruction's (phase, names), from the first of these that
    names a phase:

    1. for a fusion, the `op_name` of its fused computation's root, the
       op whose result the kernel returns (XLA gives a fusion the
       metadata of its main op, such as a dot, which may belong to an
       earlier phase than the op the kernel ends in);
    2. its own `op_name`;
    3. for a fusion, the other ops of its fused computation, nearest the
       root first;
    4. its users, then its operands, nearest first: the compiler leaves
       the ops it makes without metadata (async copies and slices,
       rewritten dots, bitcasts), and JAX names the loop invariants it
       hoists out of a differentiated scan (rope tables, masks) without
       the scopes around the scan;
    5. the instruction that calls its computation (a loop body).
    """
    rows = list(instructions(hlo_text))
    comps: Dict[str, List[str]] = {}
    own: Dict[str, Optional[Scope]] = {}
    refs: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    for comp, name, is_root, rest in rows:
        comps.setdefault(comp, []).append(name)
        m = _OP_NAME.search(rest)
        own[name] = path_scope(m.group(1)) if m else None
        refs[name] = _REF.findall(rest.split(", metadata=")[0])
        if is_root:
            roots[comp] = name
    caller: Dict[str, str] = {}
    base: Dict[str, Optional[Scope]] = {}
    for comp, name, _, rest in rows:
        callees = [r for r in refs[name] if r in comps]
        caller.update((c, name) for c in callees)
        order = [name]
        if " fusion(" in rest and callees:
            fused = callees[0]
            order = [roots.get(fused), name] + comps[fused][::-1]
        base[name] = next((own[i] for i in order if i and own[i]), None)
    up: Dict[str, Optional[Scope]] = {}
    down: Dict[str, Optional[Scope]] = {}
    for names in comps.values():  # operands print before their users
        local = set(names)
        users: Dict[str, List[str]] = {n: [] for n in names}
        for n in names:
            ops = [r for r in refs[n] if r in local]
            up[n] = base[n] or next((up[o] for o in ops if up[o]), None)
            for o in ops:
                users[o].append(n)
        for n in reversed(names):
            down[n] = base[n] or next((down[u] for u in users[n] if down[u]), None)
    out: Dict[str, Scope] = {}
    for comp in reversed(list(comps)):  # callers print after their callees
        via = out.get(caller.get(comp, ""), NO_SCOPE)
        for n in comps[comp]:
            out[n] = down[n] or up[n] or via
    return out


def op_name(event_name: str) -> str:
    """The instruction name of an XLA Ops event, `%fusion.33 = f32[...]
    fusion(...)` -> `fusion.33`."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def base_name(event_name: str) -> str:
    """The instruction name without its trailing `.N`: `ssm_scan_bwd.5`
    and `ssm_scan_bwd.6` -> `ssm_scan_bwd`."""
    return _SUFFIX.sub("", op_name(event_name))


def _innermost(ops, lo: float, hi: float):
    """[(seconds, op name)] covering the busy union of `ops` (name,
    start ns, duration ns) inside [lo, hi): each piece goes to the op
    that started last among those running, on a tie the shorter."""
    iv = sorted((max(s, lo), min(s + d, hi), s, d, n) for n, s, d in ops
                if s + d > lo and s < hi and d > 0)
    cuts = sorted({t for a, b, *_ in iv for t in (a, b)})
    out, heap, i = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(iv) and iv[i][0] <= t0:
            a, b, s, d, n = iv[i]
            heapq.heappush(heap, (-s, d, i, b, n))
            i += 1
        while heap and heap[0][3] <= t0:
            heapq.heappop(heap)
        if heap:
            out.append(((t1 - t0) * 1e-9, heap[0][4]))
    return out


def reduce(events: Dict, scopes: Dict[str, Scope]) -> Dict:
    """Per round, mean over devices: `<phase>_s`, `unscoped_s`,
    `loss_grad_s` and `busy_s` in seconds, `unscoped_share` of busy;
    `scope_s`, the busy seconds under each scope name seen, inclusive
    (an instant counts toward every name on its innermost op's path, so
    `scope_s[<phase>]` is `<phase>_s`); `kernel_s`, each op's own
    seconds inside the window summed by `base_name`, overlaps and all,
    as `trace.reduce` ranks `device_ops`; with `rounds` and `named_ops`,
    the instructions the map gives a phase (0 for a program without the
    names)."""
    host = events["host"]
    marks = [(s, s + d) for n, s, d in host if n in WINDOW_SPANS]
    rounds = sum(1 for n, _, _ in host if n == "round")
    if not marks or not rounds or not events["devices"]:
        raise ValueError("trace has no round span or no device operations")
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    n_dev = len(events["devices"])
    sums = dict.fromkeys(METRICS + ("busy_s",), 0.0)
    scope_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    for ops in events["devices"].values():
        for sec, name in _innermost(ops, lo, hi):
            phase, names = scopes.get(op_name(name), NO_SCOPE)
            sums[f"{phase}_s" if phase else "unscoped_s"] += sec
            sums["busy_s"] += sec
            for n in names:
                scope_s[n] += sec
        for name, s, d in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                kernel_s[base_name(name)] += (b - a) * 1e-9
    sums["loss_grad_s"] = scope_s.get(LOSS_GRAD, 0.0)
    per = lambda d: {k: v / n_dev / rounds for k, v in d.items()}
    out = per(sums)
    out["unscoped_share"] = out["unscoped_s"] / out["busy_s"] if out["busy_s"] else 0.0
    out["scope_s"] = per(scope_s)
    out["kernel_s"] = per(kernel_s)
    out["rounds"] = rounds
    out["named_ops"] = sum(1 for p, _ in scopes.values() if p is not None)
    return out
