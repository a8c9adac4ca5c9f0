"""Jit'd public wrappers around the Pallas kernels.

The kernels compile to Mosaic on a TPU and run in the Pallas interpreter
elsewhere (`backend.resolve_interpret`).  `make_gt_update_fn(use_kernel=
False)` falls back to the ref oracle.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .gt_update import gt_update_2d
from .ssm_scan import ssm_scan

Pytree = Any


def _to_2d(u: jax.Array):
    n = u.size
    cols = 128
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(u.reshape(-1), (0, pad))
    return flat.reshape(rows, cols), pad


def make_gt_update_fn(use_kernel: bool = True):
    """Drop-in `update_fn` for core.fedgda_gt.make_fedgda_gt_round."""

    def update(z: Pytree, g: Pytree, c: Pytree, eta, sign: float) -> Pytree:
        if not use_kernel:
            return jax.tree.map(
                lambda u, gv, cv: ref.gt_update_ref(u, gv, cv, eta, sign), z, g, c
            )

        def leaf(u, gv, cv):
            u2, pad = _to_2d(u)
            g2, _ = _to_2d(gv)
            c2, _ = _to_2d(cv.astype(gv.dtype))
            r = gt_update_2d(
                u2, g2, c2, eta=float(eta), sign=sign,
                block_rows=min(256, u2.shape[0]),
            )
            return r.reshape(-1)[: u.size].reshape(u.shape)

        return jax.tree.map(leaf, z, g, c)

    return update


def grouped_flash_attention(
    q: jax.Array,  # [B, Sq, H, hd] (model layout)
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """GQA adapter: repeats KV groups, runs the kernel, restores layout."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qt = q.transpose(0, 2, 1, 3)  # [B,H,Sq,hd]
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    out = flash_attention(
        qt, kt, vt, causal=causal, window=window, softcap=softcap
    )
    return out.transpose(0, 2, 1, 3)


def batched_ssm_scan(
    da: jax.Array,  # [B, S, D, N]
    dbx: jax.Array,
    c_coef: jax.Array,  # [B, S, N]
    *,
    chunk: int = 64,
) -> jax.Array:
    fn = functools.partial(ssm_scan, chunk=chunk)
    return jax.vmap(fn)(da, dbx, c_coef)
