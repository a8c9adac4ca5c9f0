"""Fused FedGDA-GT inner-loop update kernel.

z <- z + sign * eta * (g + c): three HBM-resident arrays (params, gradient,
tracking correction — the correction may be a narrower dtype, e.g. fp8) are
streamed through VMEM once and written back fused, instead of the three
separate elementwise passes XLA would otherwise schedule around the dtype
conversion.  Tiles are (block_rows, 128) — lane-aligned for the VPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _gt_update_kernel(z_ref, g_ref, c_ref, o_ref, *, eta: float, sign: float):
    z = z_ref[...]
    g = g_ref[...]
    c = c_ref[...].astype(jnp.float32)
    upd = z.astype(jnp.float32) + sign * eta * (g.astype(jnp.float32) + c)
    o_ref[...] = upd.astype(o_ref.dtype)


def gt_update_2d(
    z: jax.Array,  # [R, C], C % 128 == 0
    g: jax.Array,
    c: jax.Array,
    *,
    eta: float,
    sign: float,
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    R, C = z.shape
    br = min(block_rows, R)
    bc = min(block_cols, C)
    assert R % br == 0 and C % bc == 0, (z.shape, br, bc)
    grid = (R // br, C // bc)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_gt_update_kernel, eta=eta, sign=sign),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=resolve_interpret(interpret),
    )(z, g, c)
