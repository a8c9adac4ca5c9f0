"""Fused selective scan (Mamba-1) with its own backward.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t,   y_t = sum_n C_t[n] h_t[n]

for dt, x [Bt, S, D], A [D, N], B, C [Bt, S, N], from h_{-1} = 0, all in
float32.

Forward grid (batch, d-blocks, time chunks): the chunk axis is innermost
and sequential, and the state lives in VMEM scratch across it as an
[N, block_d] tile, channels on the 128 lanes and the N states on
sublanes.  exp(dt A) and dt x B are formed in registers step by step, so
nothing of shape [S, D, N] reaches HBM.  Beside y the forward writes the
state entering each chunk, [Bt, S/chunk, N, D]: the backward's only
residual beyond the inputs.

The backward runs the chunks in reverse.  It recomputes a chunk's states
from the saved entry state into VMEM, then runs the adjoint
g_t = C_t dy_t + exp(dt_{t+1} A) g_{t+1} backwards in time.  It writes
ddt and dx [Bt, S, D] directly, dB and dC as partial sums per d-block
(summed by the caller's XLA), and accumulates dA per batch row over the
chunks.

Time steps go in groups of 8, one sublane tile: dt, x and dy arrive as
lane-dense [8, block_d] rows and the per-step rows of ddt, dx and y are
gathered into such tiles before they are stored.  B and C arrive as
[Bt, S, N, 1], so that B_t is an [N, 1] column that broadcasts over the
lanes; dB and dC leave as [N, chunk] tiles filled one column per step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

F32 = jnp.float32
_ROWS = 8  # time steps per sublane tile


def _time_tile(S: int) -> Optional[int]:
    """The kernel's time chunk for S steps, or None where S does not
    tile (S == 1, or not a multiple of 8)."""
    if S <= 1:
        return None
    return next((t for t in (64, 32, 16, 8) if S % t == 0), None)


def _channel_block(D: int) -> Optional[int]:
    """The widest lane-aligned d-block up to 1024 that divides D.  At a
    chunk of 64 and 1024 channels the backward's working set (the
    recomputed states, 4.3 MB, and its double-buffered tiles) takes
    about 9 MB of the 16 MiB of scoped VMEM; on a v5e the wider blocks
    ran faster than 512 or 256 (PERF.md, PR 14)."""
    return next((b for b in (1024, 512, 256, 128) if D % b == 0), None)


def fits(S: int, D: int) -> bool:
    """Whether S steps of D channels tile into the kernels."""
    return bool(_time_tile(S) and _channel_block(D))


def _fwd_kernel(dt_ref, x_ref, at_ref, b_ref, c_ref, y_ref, h0_ref, h_scr,
                *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    h0_ref[...] = h_scr[...]
    at = at_ref[...]  # [N, bd]
    rows = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, at.shape[1]), 0)

    def group(k, h):
        t0 = pl.multiple_of(k * _ROWS, _ROWS)
        dt8 = dt_ref[pl.ds(t0, _ROWS), :]
        dtx8 = dt8 * x_ref[pl.ds(t0, _ROWS), :]
        y8 = jnp.zeros_like(dt8)
        for i in range(_ROWS):
            h = (jnp.exp(dt8[i:i + 1] * at) * h
                 + b_ref[t0 + i] * dtx8[i:i + 1])
            y = jnp.sum(h * c_ref[t0 + i], axis=0, keepdims=True)
            y8 = jnp.where(rows == i, y, y8)
        y_ref[pl.ds(t0, _ROWS), :] = y8
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // _ROWS, group, h_scr[...])


def _bwd_kernel(dt_ref, x_ref, at_ref, b_ref, c_ref, dy_ref, h0_ref,
                ddt_ref, dx_ref, dat_ref, db_ref, dc_ref, hs_scr, g_scr,
                *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        dat_ref[...] = jnp.zeros_like(dat_ref)

    at = at_ref[...]  # [N, bd]
    N, bd = at.shape
    groups = chunk // _ROWS

    # the chunk's states: hs_scr[0] enters it, hs_scr[t + 1] = h_t
    hs_scr[0] = h0_ref[...]

    def recompute(k, h):
        t0 = pl.multiple_of(k * _ROWS, _ROWS)
        dt8 = dt_ref[pl.ds(t0, _ROWS), :]
        dtx8 = dt8 * x_ref[pl.ds(t0, _ROWS), :]
        for i in range(_ROWS):
            h = (jnp.exp(dt8[i:i + 1] * at) * h
                 + b_ref[t0 + i] * dtx8[i:i + 1])
            hs_scr[t0 + i + 1] = h
        return h

    jax.lax.fori_loop(0, groups, recompute, hs_scr[0])

    rows = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, bd), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (N, chunk), 1)

    def adjoint(k, carry):
        gd, dat, db, dc = carry  # gd = exp(dt_{t+1} A) g_{t+1}
        t0 = pl.multiple_of((groups - 1 - k) * _ROWS, _ROWS)
        dt8 = dt_ref[pl.ds(t0, _ROWS), :]
        x8 = x_ref[pl.ds(t0, _ROWS), :]
        dy8 = dy_ref[pl.ds(t0, _ROWS), :]
        ddt8 = jnp.zeros_like(dt8)
        dx8 = jnp.zeros_like(dt8)
        for i in reversed(range(_ROWS)):
            t = t0 + i
            dt, x, dy = dt8[i:i + 1], x8[i:i + 1], dy8[i:i + 1]
            h, h_prev = hs_scr[t + 1], hs_scr[t]
            a = jnp.exp(dt * at)
            g = gd + c_ref[t] * dy
            dc = jnp.where(cols == t,
                           jnp.sum(h * dy, axis=1, keepdims=True), dc)
            db = jnp.where(cols == t,
                           jnp.sum(g * (dt * x), axis=1, keepdims=True), db)
            gb = jnp.sum(g * b_ref[t], axis=0, keepdims=True)  # [1, bd]
            q = g * a * h_prev  # dL/d(exp(dt A)) * exp(dt A)
            ddt8 = jnp.where(
                rows == i, gb * x + jnp.sum(q * at, axis=0, keepdims=True),
                ddt8)
            dx8 = jnp.where(rows == i, gb * dt, dx8)
            dat = dat + q * dt
            gd = a * g
        ddt_ref[pl.ds(t0, _ROWS), :] = ddt8
        dx_ref[pl.ds(t0, _ROWS), :] = dx8
        return gd, dat, db, dc

    zeros_nc = jnp.zeros((N, chunk), F32)
    gd, dat, db, dc = jax.lax.fori_loop(
        0, groups, adjoint,
        (g_scr[...], jnp.zeros_like(at), zeros_nc, zeros_nc))
    g_scr[...] = gd
    dat_ref[...] += dat
    db_ref[...] = db
    dc_ref[...] = dc


def _forward(cfg, dt, x, at, b4, c4):
    chunk, bd, interpret = cfg
    Bt, S, D = dt.shape
    N = at.shape[0]
    nc = S // chunk
    row = pl.BlockSpec((None, chunk, bd), lambda b, d, c: (b, c, d))
    col = pl.BlockSpec((None, chunk, N, 1), lambda b, d, c: (b, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(Bt, D // bd, nc),
        in_specs=[row, row, pl.BlockSpec((N, bd), lambda b, d, c: (0, d)),
                  col, col],
        out_specs=[row, pl.BlockSpec((None, None, N, bd),
                                     lambda b, d, c: (b, c, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((Bt, S, D), F32),
                   jax.ShapeDtypeStruct((Bt, nc, N, D), F32)],
        scratch_shapes=[pltpu.VMEM((N, bd), F32)],
        interpret=interpret,
        name="ssm_scan_fwd",
    )(dt, x, at, b4, c4)


def _backward(cfg, dt, x, at, b4, c4, dy, h0s):
    chunk, bd, interpret = cfg
    Bt, S, D = dt.shape
    N = at.shape[0]
    nc, nd = S // chunk, D // bd

    def rev(c):
        return nc - 1 - c

    row = pl.BlockSpec((None, chunk, bd), lambda b, d, c: (b, rev(c), d))
    col = pl.BlockSpec((None, chunk, N, 1), lambda b, d, c: (b, rev(c), 0, 0))
    at_spec = pl.BlockSpec((N, bd), lambda b, d, c: (0, d))
    part = pl.BlockSpec((None, None, None, N, chunk),
                        lambda b, d, c: (b, d, rev(c), 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(Bt, nd, nc),
        in_specs=[row, row, at_spec, col, col, row,
                  pl.BlockSpec((None, None, N, bd),
                               lambda b, d, c: (b, rev(c), 0, d))],
        out_specs=[row, row,
                   pl.BlockSpec((None, N, bd), lambda b, d, c: (b, 0, d)),
                   part, part],
        out_shape=[jax.ShapeDtypeStruct((Bt, S, D), F32),
                   jax.ShapeDtypeStruct((Bt, S, D), F32),
                   jax.ShapeDtypeStruct((Bt, N, D), F32),
                   jax.ShapeDtypeStruct((Bt, nd, nc, N, chunk), F32),
                   jax.ShapeDtypeStruct((Bt, nd, nc, N, chunk), F32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, N, bd), F32),
                        pltpu.VMEM((N, bd), F32)],
        interpret=interpret,
        name="ssm_scan_bwd",
    )(dt, x, at, b4, c4, dy, h0s)


def _rows(part):
    """dB or dC partial sums [Bt, nd, nc, N, chunk] -> [Bt, S, N]."""
    Bt, _, nc, N, chunk = part.shape
    return part.sum(axis=1).swapaxes(-1, -2).reshape(Bt, nc * chunk, N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(cfg, dt, x, A, Bc, Cc):
    y, _ = _forward(cfg, dt, x, A.T, Bc[..., None], Cc[..., None])
    return y


def _scan_fwd(cfg, dt, x, A, Bc, Cc):
    y, h0s = _forward(cfg, dt, x, A.T, Bc[..., None], Cc[..., None])
    return y, (dt, x, A, Bc, Cc, h0s)


def _scan_bwd(cfg, res, dy):
    dt, x, A, Bc, Cc, h0s = res
    with jax.named_scope("ssm_scan"):
        ddt, dx, dat, db, dc = _backward(
            cfg, dt, x, A.T, Bc[..., None], Cc[..., None], dy, h0s)
    return ddt, dx, dat.sum(axis=0).T, _rows(db), _rows(dc)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    dt: jax.Array,  # [Bt, S, D]
    x: jax.Array,  # [Bt, S, D]
    A: jax.Array,  # [D, N]
    Bc: jax.Array,  # [Bt, S, N]
    Cc: jax.Array,  # [Bt, S, N]
    *,
    chunk: Optional[int] = None,
    block_d: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y [Bt, S, D] float32 of the selective scan from a zero state,
    differentiable in every input.  The tiling changes only the order of
    the sums over d-blocks (dB, dC) and over chunks (dA): `chunk`
    (time steps per grid step, a multiple of 8 dividing S) defaults to
    the largest of 64, 32, 16, 8 that divides S; `block_d` (channels per
    grid step in the forward and the backward, a multiple of 128
    dividing D) to the widest up to 1024.  `fits(S, D)` says whether
    the defaults exist."""
    S, D = dt.shape[1], dt.shape[2]
    chunk = chunk or _time_tile(S)
    bd = block_d or _channel_block(D)
    if not chunk or chunk % _ROWS or S % chunk or not bd or D % bd:
        raise ValueError(f"selective_scan: S={S}, D={D} do not tile "
                         f"(chunk {chunk}, d-block {bd})")
    cfg = (chunk, bd, resolve_interpret(interpret))
    with jax.named_scope("ssm_scan"):
        return _scan(cfg, *(u.astype(F32) for u in (dt, x, A, Bc, Cc)))
