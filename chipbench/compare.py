"""The comparison that decides `correct`.

Each number compares the program's round with the plain reference
(`reference.py`) on the same seed, leaf by leaf:

- `update1_gap`: the first round's update, `z1 - z0`, of every leaf of
  the parameters and of `delta`;
- `change3_gap`: the change after three rounds, `z3 - z0`.

For a leaf the gap is `| |p| - |r| |` between the norms of the program's
and the reference's change, over the reference's norm of that leaf or of
the median leaf, whichever is larger; the number is the worst leaf.  A
leaf whose reference update in round 1 is under a thousandth of the
median leaf's moves by round-off alone and is left out.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

EXCLUDE_BELOW = 1e-3


@jax.jit
def _diff_norms(z, z0):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                                 - b.astype(jnp.float32)))),
        z, z0)


def change_norms(x, y, x0, y0) -> Dict[str, float]:
    """Per-leaf norms of (x - x0, y - y0), keyed by leaf path."""
    out = _diff_norms((x, y), (x0, y0))
    flat = jax.tree_util.tree_flatten_with_path(out)[0]
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def included(ref_first: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_first.values())
    return sorted(k for k, v in ref_first.items() if v >= EXCLUDE_BELOW * med)


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keys: List[str]) -> Tuple[float, str]:
    """(worst-leaf gap of norms, that leaf's path)."""
    med = statistics.median(ref[k] for k in keys)
    worst, leaf = -1.0, ""
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not g <= worst:  # a NaN norm is the worst leaf
            worst, leaf = g, k
    return worst, leaf


def readings(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """The compared numbers of one run.  `prog` and `ref` each hold
    `update1` and `change3` (leaf norms)."""
    keys = included(ref["update1"])
    out = {}
    for name in ("update1", "change3"):
        gap, leaf = norm_gap(prog[name], ref[name], keys)
        out[f"{name}_gap"] = {"value": gap, "leaf": leaf}
    return out


def judge(read: Dict[str, Dict], limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """Hold every number to its limit.  A missing or non-finite number
    fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v: Optional[float] = read.get(name, {}).get("value")
        passed = v is not None and v == v and v <= limit
        ok &= passed
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
