"""Smoke check: the federated training round runs on a TPU.

Drives `repro.launch.train.main`, the entry point a user calls, once at
granite-8b's published widths (d_model 4096, 32 query / 8 KV heads,
head_dim 128, d_ff 14336, the full 49152 vocabulary), cut to one layer,
with random weights from a seed: 2 agents, 1 x 2048 tokens each, K = 2
local steps, 3 FedGDA-GT rounds on one chip.  It checks that every logged
loss is finite and the last is below the first, and that the
gradient-tracking invariant sum_i c_i = 0 holds at the final iterate to
float32 round-off.

`--chips 4` runs only the four-chip phase: the async runtime with 4
agents, one per chip, on a 1/8 vocabulary (6144), against the sync fused
round of the same problem on one chip; the iterates after 2 rounds and
the losses must agree to float32 tolerance.  Both run with float32
matmuls at full precision: at a v5e's default (bf16 passes) the two
programs round differently and differ by about 2e-2 of the update.

The timings printed are smoke timings of a handful of rounds, not
benchmark numbers.  The last line of output is a JSON object naming the
device; the script exits non-zero, before printing it, when JAX finds no
TPU or any check fails.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ONE_CHIP = [
    "--arch", "granite-8b", "--num-layers", "1", "--agents", "2",
    "--per-agent-batch", "1", "--seq-len", "2048", "--local-steps", "2",
    "--rounds", "3", "--algorithm", "fedgda_gt", "--log-every", "1",
]
FOUR_CHIPS = [
    "--arch", "granite-8b", "--num-layers", "1", "--vocab-size", "6144",
    "--agents", "4", "--per-agent-batch", "1", "--seq-len", "2048",
    "--local-steps", "2", "--rounds", "2", "--algorithm", "fedgda_gt",
    "--log-every", "1",
]

# ||sum_i c_i|| / sum_i ||c_i||.  Each c_i = gbar - g_i is one rounded
# float32 subtraction from a rounded mean, so every entry of sum_i c_i is
# a few ulps (~1e-7 relative) of the gradients; with heterogeneous agents
# ||c_i|| is of the order of ||g_i||, so the ratio sits near 1e-7.  1e-5
# leaves two orders of headroom, while a broken exchange (stale or
# misweighted gradients) gives a ratio of order one.
GT_RESIDUAL_TOL = 1e-5
# async vs sync, both at float32 matmul precision: the same per-agent
# math, but gbar is summed in another order (per-shard partial sums
# against one mean) and a one-agent shard's program accumulates its
# reductions (up to d_ff = 14336 terms) in another order than the
# 4-agent batch.  The iterates are compared relative to the update the
# sync run made (||x_async - x_sync|| / ||x_sync - x_0||): float32
# accumulation differences measured 1.1e-4 of it on a v5e, bf16 matmul
# passes 2e-2, and a wrong correction or weight is of order one.  1e-3
# sits between float32 and bf16 arithmetic.
UPDATE_TOL = 1e-3
# the loss is the same jitted program on nearly equal iterates
LOSS_RTOL = 1e-5


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check_losses(losses) -> None:
    print(f"losses: {losses}")
    if not losses or not all(math.isfinite(v) for v in losses):
        fail(f"non-finite or missing losses {losses}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not fall: first {losses[0]}, last {losses[-1]}")


def memory(device) -> str:
    """The device's peak so far.  `peak_bytes_in_use` counts live
    arrays; a program's scratch space shows only in
    `peak_bytes_reserved`, so both are printed."""
    stats = device.memory_stats()
    return (f"peak_bytes_in_use {stats['peak_bytes_in_use']}, "
            f"peak_bytes_reserved {stats.get('peak_bytes_reserved')}, "
            f"bytes_limit {stats.get('bytes_limit')}")


def print_timings(res) -> None:
    if res.compile_seconds is not None:
        print(f"compile_seconds: {res.compile_seconds:.3f}")
    print("round_seconds (smoke timing, not a benchmark number): "
          + ", ".join(f"{s:.4f}" for s in res.round_seconds))


def gt_relative_residual(res) -> float:
    """||sum_i c_i|| over sum_i ||c_i|| for the anchor corrections at
    the final iterate (`repro.obs.probes`)."""
    from repro.core.types import grad_xy
    from repro.obs import probes

    gfn = grad_xy(res.loss_fn)

    @jax.jit
    def corrections(x, y, data):
        cx, cy = probes.anchor_corrections(gfn, x, y, data)
        leaves = jax.tree.leaves((cx, cy))
        per_agent = sum(
            jnp.sum(jnp.square(u).reshape(u.shape[0], -1), axis=1)
            for u in leaves
        )
        return cx, cy, jnp.sqrt(per_agent)

    cx, cy, norms = corrections(res.params, res.delta, res.data)
    return probes.gt_residual(cx, cy) / float(jnp.sum(norms))


def one_chip() -> None:
    from repro.launch import train

    res = train.main(ONE_CHIP)
    print_timings(res)
    check_losses(res.losses)
    rel = gt_relative_residual(res)
    print(f"gt_residual ||sum c_i|| / sum ||c_i||: {rel:.3e} "
          f"(tolerance {GT_RESIDUAL_TOL:.0e})")
    if not rel <= GT_RESIDUAL_TOL:
        fail(f"gradient-tracking invariant broken: {rel:.3e}")
    print(f"device 0: {memory(jax.devices()[0])}")


def _host(tree):
    return [np.asarray(u, np.float64) for u in jax.tree.leaves(tree)]


def _norm(leaves) -> float:
    return math.sqrt(sum(float(np.sum(np.square(u))) for u in leaves))


def four_chips() -> None:
    from repro.launch import train
    from repro.models import init_params

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, JAX found {len(jax.devices())}")
    with jax.default_matmul_precision("highest"):
        asy = train.main(FOUR_CHIPS + ["--runtime", "async"])
    print_timings(asy)
    check_losses(asy.losses)
    for d in jax.devices():
        print(f"async, device {d.id}: {memory(d)}")
    xa, ya, la = _host(asy.params), _host(asy.delta), asy.losses
    del asy

    with jax.default_matmul_precision("highest"):
        syn = train.main(FOUR_CHIPS)
    print_timings(syn)
    check_losses(syn.losses)
    print(f"sync, device 0: {memory(jax.devices()[0])}")
    xs, ys = _host(syn.params), _host(syn.delta)
    x0 = _host(init_params(jax.random.PRNGKey(0), syn.cfg, jnp.float32))
    dx = _norm([a - b for a, b in zip(xa, xs)]) / _norm(
        [a - b for a, b in zip(xs, x0)]
    )
    # delta starts at zero, so its own norm is the update it made
    dy = _norm([a - b for a, b in zip(ya, ys)]) / _norm(ys)
    dl = max(abs(a - b) / abs(b) for a, b in zip(la, syn.losses))
    print(f"async vs sync: params {dx:.3e}, delta {dy:.3e} of the update "
          f"(tolerance {UPDATE_TOL:.0e}); losses {dl:.3e} relative "
          f"(tolerance {LOSS_RTOL:.0e})")
    if not (dx <= UPDATE_TOL and dy <= UPDATE_TOL and dl <= LOSS_RTOL):
        fail("async and sync rounds disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip async-vs-sync phase")
    args = ap.parse_args()
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found backend {backend!r}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
