"""Chunked selective-scan (Mamba) kernel.

h_t = da_t * h_{t-1} + dbx_t;   y_t = <h_t, c_t>

Grid (d_blocks, chunks): the channel axis is parallel; the chunk axis is the
innermost sequential dimension with the carried state [d_block, N] living in
VMEM scratch across chunks.  Inside a chunk the recurrence runs as a fori
loop over time steps on VMEM-resident tiles — the working set is
O(chunk * d_block * N) regardless of sequence length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


def _ssm_kernel(da_ref, dbx_ref, c_ref, y_ref, h_scr, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a = da_ref[t]  # [d_block, N]
        b = dbx_ref[t]
        cc = c_ref[t]  # [1, N]
        h = a * h + b
        y_ref[t] = jnp.sum(h * cc, axis=-1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h


def ssm_scan(
    da: jax.Array,  # [S, D, N] float32 (decay factors, broadcast-expanded)
    dbx: jax.Array,  # [S, D, N] float32
    c_coef: jax.Array,  # [S, N] float32
    *,
    chunk: int = 64,
    block_d: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns y [S, D].  Single sequence; vmap over batch."""
    S, D, N = da.shape
    # the kernel computes in float32 (VMEM scratch dtype); normalize inputs
    da = da.astype(jnp.float32)
    dbx = dbx.astype(jnp.float32)
    c_coef = c_coef.astype(jnp.float32)
    chunk = min(chunk, S)
    block_d = min(block_d, D)
    assert S % chunk == 0 and D % block_d == 0, (da.shape, chunk, block_d)
    grid = (D // block_d, S // chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, block_d, N), lambda d, c: (c, d, 0)),
            pl.BlockSpec((chunk, block_d, N), lambda d, c: (c, d, 0)),
            pl.BlockSpec((chunk, 1, N), lambda d, c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, block_d), lambda d, c: (c, d)),
        out_shape=jax.ShapeDtypeStruct((S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(da, dbx, c_coef.reshape(S, 1, N))
