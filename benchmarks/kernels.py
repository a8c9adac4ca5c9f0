"""Kernel validation + host microbenchmark table.

For each Pallas kernel: max |err| vs the ref.py oracle at a model-relevant
shape (interpret=True on CPU — functional validation), plus the host wall
time of the jnp reference path (the numbers that matter on TPU come from the
roofline, not from CPU timings)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    compress_correction_2d,
    flash_attention,
    gt_update_2d,
    pack_payload_2d,
    ref,
    selective_scan,
)

from .common import emit, timed


def run(rows=None):
    rows = [] if rows is None else rows
    key = jax.random.PRNGKey(0)

    # gt_update: one tile of a parameter shard
    z, g, c = (jax.random.normal(k, (512, 512), jnp.float32)
               for k in jax.random.split(key, 3))
    got = gt_update_2d(z, g, c, eta=1e-3, sign=-1.0, interpret=True)
    want = ref.gt_update_ref(z, g, c, 1e-3, -1.0)
    rfn = jax.jit(lambda a, b, d: ref.gt_update_ref(a, b, d, 1e-3, -1.0))
    rfn(z, g, c).block_until_ready()
    rows.append({
        "kernel": "gt_update(512x512 f32)",
        "max_abs_err_vs_ref": f"{float(jnp.max(jnp.abs(got - want))):.2e}",
        "ref_us_per_call": f"{timed(lambda: rfn(z, g, c).block_until_ready()):.0f}",
    })

    # compress_correction: a 20-agent correction leaf, top-10% + 8-bit QSGD
    kc, ke, ku = jax.random.split(jax.random.fold_in(key, 1), 3)
    R, C, kk = 20, 4096, 410
    c, e = jax.random.normal(kc, (R, C)), 0.1 * jax.random.normal(ke, (R, C))
    ur = jax.random.uniform(ku, (R, C))
    got = compress_correction_2d(c, e, None, ur, k=kk, bits=8, interpret=True)
    want = ref.compress_correction_ref(c, e, None, ur, k=kk, bits=8)
    rfn = jax.jit(
        lambda a, b, u: ref.compress_correction_ref(a, b, None, u, k=kk, bits=8)
    )
    rfn(c, e, ur)[0].block_until_ready()
    rows.append({
        "kernel": "compress_correction(20x4096 f32, top-10% 8-bit+EF)",
        "max_abs_err_vs_ref": f"{float(max(jnp.max(jnp.abs(g - w)) for g, w in zip(got, want))):.2e}",
        "ref_us_per_call": f"{timed(lambda: rfn(c, e, ur)[0].block_until_ready()):.0f}",
    })

    # pack_payload: same leaf, packed to the actual wire format
    got = pack_payload_2d(
        c, e, None, ur, k=kk, bits=8, encoding="quant", interpret=True
    )
    want = ref.pack_payload_ref(c, e, None, ur, k=kk, bits=8, encoding="quant")
    rfn = jax.jit(
        lambda a, b, u: ref.pack_payload_ref(
            a, b, None, u, k=kk, bits=8, encoding="quant"
        )
    )
    rfn(c, e, ur)[0].block_until_ready()
    rows.append({
        "kernel": "pack_payload(20x4096 f32, top-10% 8-bit, uint32 words)",
        "max_abs_err_vs_ref": f"{max(float(np.max(np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64)))) for g, w in zip(got, want)):.2e}",
        "ref_us_per_call": f"{timed(lambda: rfn(c, e, ur)[0].block_until_ready()):.0f}",
    })

    # flash attention: gemma2-like tile
    q, k_, v = (jax.random.normal(kk, (1, 4, 512, 128), jnp.float32)
                for kk in jax.random.split(key, 3))
    got = flash_attention(q, k_, v, causal=True, window=256, interpret=True)
    want = ref.flash_attention_ref(q, k_, v, causal=True, window=256)
    rfn = jax.jit(lambda a, b, d: ref.flash_attention_ref(a, b, d, causal=True, window=256))
    rfn(q, k_, v).block_until_ready()
    rows.append({
        "kernel": "flash_attention(B1 H4 S512 hd128, win=256)",
        "max_abs_err_vs_ref": f"{float(jnp.max(jnp.abs(got - want))):.2e}",
        "ref_us_per_call": f"{timed(lambda: rfn(q, k_, v).block_until_ready()):.0f}",
    })

    # selective scan: falcon-mamba-like tile (state 16), from a zero state
    k1, k2, k3, k4 = jax.random.split(key, 4)
    S, D, N = 256, 256, 16
    dt = jax.nn.softplus(jax.random.normal(k1, (1, S, D)) - 2.0)
    x = jax.random.normal(k2, (1, S, D))
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (D, N))
    bc, cc = (jax.random.normal(kk, (1, S, N)) for kk in (k3, k4))
    got = selective_scan(dt, x, A, bc, cc, interpret=True)
    want, _ = ref.ssm_scan_ref(dt, x, A, bc, cc)
    rfn = jax.jit(lambda *a: ref.ssm_scan_ref(*a)[0])
    rfn(dt, x, A, bc, cc).block_until_ready()
    rows.append({
        "kernel": "selective_scan(S256 D256 N16)",
        "max_abs_err_vs_ref": f"{float(jnp.max(jnp.abs(got - want))):.2e}",
        "ref_us_per_call": f"{timed(lambda: rfn(dt, x, A, bc, cc).block_until_ready()):.0f}",
    })

    emit(rows, ["kernel", "max_abs_err_vs_ref", "ref_us_per_call"],
         "Pallas kernels vs ref oracles (interpret=True on CPU)")
    return rows


if __name__ == "__main__":
    run()
