"""Mamba-1 (selective scan) and Mamba-2 (SSD, scalar-per-head decay) blocks.

Unified state layout [B, n_heads, head_p, d_state]:
  * mamba1: n_heads = d_inner, head_p = 1, A in R^{d_inner x N} (per-channel).
  * mamba2: n_heads = d_inner/head_p, A scalar per head.

The jnp sequence scan is CHUNKED: an associative scan runs inside
fixed-size chunks (`ModelConfig.ssm_chunk`) while a lax.scan carries the
[B, nh, p, N] state across chunks.  This bounds live memory to
O(B * chunk * d_inner * N) instead of O(B * S * d_inner * N).

Built for one TPU device (`kernels.backend.on_one_tpu`), a mamba1 block
without a cache whose shape tiles (`kernels.ssm_scan.fits`) runs the
fused Pallas `selective_scan` instead, forward and backward, which never
writes a [S, d_inner, N] tensor to HBM.  Every other platform, a program
partitioned over a mesh of TPUs, mamba2, the decode step and the
cache-prefixed prefill take the jnp scan.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import backend, ssm_scan


def init_mamba(
    key,
    d: int,
    d_inner: int,
    d_state: int,
    conv_width: int,
    variant: str,
    dtype,
    head_p: int = 64,
    dt_rank: Optional[int] = None,
) -> Dict:
    ks = jax.random.split(key, 8)
    s_in = 1.0 / jnp.sqrt(d)
    s_inner = 1.0 / jnp.sqrt(d_inner)
    dt_rank = dt_rank or max(1, d // 16)
    nh = d_inner if variant == "mamba1" else d_inner // head_p
    p = {
        "in_proj": (jax.random.normal(ks[0], (d, 2 * d_inner)) * s_in).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_width, d_inner)) * 0.5).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "out_proj": (jax.random.normal(ks[2], (d_inner, d)) * s_inner).astype(dtype),
        "D": jnp.ones((d_inner,), dtype),
        "norm": jnp.zeros((d_inner,), dtype),
    }
    if variant == "mamba1":
        p["x_proj"] = (
            jax.random.normal(ks[3], (d_inner, dt_rank + 2 * d_state)) * s_inner
        ).astype(dtype)
        p["dt_proj"] = (
            jax.random.normal(ks[4], (dt_rank, d_inner)) / jnp.sqrt(dt_rank)
        ).astype(dtype)
        p["dt_bias"] = jnp.zeros((d_inner,), dtype)
        p["A_log"] = jnp.log(
            jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32), (d_inner, d_state))
        ).astype(jnp.float32)
    elif variant == "mamba2":
        p["bcdt_proj"] = (
            jax.random.normal(ks[3], (d, 2 * d_state + nh)) * s_in
        ).astype(dtype)
        p["dt_bias"] = jnp.zeros((nh,), dtype)
        p["A_log"] = jnp.zeros((nh,), jnp.float32)
    else:
        raise ValueError(variant)
    return p


def _chunked_scan(da, dbx, state, chunk):
    """h_t = da_t * h_{t-1} + dbx_t, scanned over axis 1 (seq).

    da: [B,S,nh,1,Na] (Na = N or 1), dbx: [B,S,nh,p,N], state: [B,nh,p,N].
    Returns (hs [B,S,nh,p,N], final state).
    """
    B, S = dbx.shape[:2]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    da_c = da.reshape(B, nc, chunk, *da.shape[2:]).swapaxes(0, 1)
    dbx_c = dbx.reshape(B, nc, chunk, *dbx.shape[2:]).swapaxes(0, 1)

    def comb(left, right):
        la, lb = left
        ra, rb = right
        return (ra * la, ra * lb + rb)

    def chunk_fn(st, inp):
        dac, dbxc = inp  # [B,c,...]
        aa, bb = jax.lax.associative_scan(comb, (dac, dbxc), axis=1)
        hs = aa * st[:, None] + bb
        return hs[:, -1], hs

    state, hs = jax.lax.scan(chunk_fn, state, (da_c, dbx_c))
    return hs.swapaxes(0, 1).reshape(B, S, *dbx.shape[2:]), state


def _scan_states(da, dbx, state0, chunk):
    """The jnp scan: states hs [B,S,nh,p,N] from state0, one step at
    S == 1, else chunked; and the final state."""
    if dbx.shape[1] == 1:
        h1 = da[:, 0] * state0 + dbx[:, 0]
        return h1[:, None], h1
    return _chunked_scan(
        da, dbx.astype(jnp.float32), state0, min(chunk, dbx.shape[1])
    )


def _mamba1_scan(dt, x, A, Bc, Cc, *, state0, chunk):
    """The jnp mamba1 scan: y [B,S,di] float32 and the final state."""
    B, S, d_inner = x.shape
    d_state = A.shape[1]
    da = jnp.exp(dt.astype(jnp.float32)[..., None] * A)  # [B,S,di,N]
    da = da[..., None, :].reshape(B, S, d_inner, 1, d_state)
    dbx = dt[..., None] * x[..., None] * Bc[:, :, None, :]  # [B,S,di,N]
    dbx = dbx.reshape(B, S, d_inner, 1, d_state)
    hs, state = _scan_states(da, dbx, state0, chunk)
    y = jnp.einsum("bsnpN,bsN->bsnp", hs, Cc.astype(jnp.float32))
    return y.reshape(B, S, d_inner), state


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv; x [B,S,di], w [W,di]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp,
        w[:, None, :],  # [W, 1, di]
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return out + b


def mamba_block(
    params: Dict,
    u: jax.Array,  # [B, S, d]
    *,
    variant: str,
    d_state: int,
    head_p: int = 64,
    chunk: int = 256,
    cache: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (output [B,S,d], updated cache or None).

    cache (decode): {"conv": [B, W-1, di], "ssm": [B, nh, p, N]}.
    """
    B, S, d = u.shape
    d_inner = params["in_proj"].shape[1] // 2
    nh = d_inner if variant == "mamba1" else d_inner // head_p
    p_dim = 1 if variant == "mamba1" else head_p

    xz = u @ params["in_proj"]
    x, z = jnp.split(xz, 2, axis=-1)

    W = params["conv_w"].shape[0]
    if cache is not None:
        xw = jnp.concatenate([cache["conv"], x], axis=1)  # [B, W-1+S, di]
        new_conv = xw[:, -(W - 1):]
        if S == 1:
            x = (
                jnp.einsum("bwd,wd->bd", xw[:, -W:], params["conv_w"])
                + params["conv_b"]
            )[:, None]
        else:  # prefill: valid conv over the cache-prefixed window
            x = jax.lax.conv_general_dilated(
                xw,
                params["conv_w"][:, None, :],
                window_strides=(1,),
                padding="VALID",
                dimension_numbers=("NWC", "WIO", "NWC"),
                feature_group_count=x.shape[-1],
            ) + params["conv_b"]
    else:
        new_conv = None
        x = _causal_conv(x, params["conv_w"], params["conv_b"])
    x = jax.nn.silu(x)

    state0 = (
        cache["ssm"]
        if cache is not None
        else jnp.zeros((B, nh, p_dim, d_state), jnp.float32)
    )
    if variant == "mamba1":
        dbl = x @ params["x_proj"]
        dt_rank = params["dt_proj"].shape[0]
        dt_raw, Bc, Cc = jnp.split(dbl, [dt_rank, dt_rank + d_state], axis=-1)
        dt = jax.nn.softplus(dt_raw @ params["dt_proj"] + params["dt_bias"])
        A = -jnp.exp(params["A_log"])  # [di, N]
        if (cache is None and backend.on_one_tpu()
                and ssm_scan.fits(S, d_inner)):
            y, state = ssm_scan.selective_scan(dt, x, A, Bc, Cc), None
        else:
            y, state = _mamba1_scan(
                dt, x, A, Bc, Cc, state0=state0, chunk=chunk
            )
    else:  # mamba2
        bcd = u @ params["bcdt_proj"]
        Bc, Cc, dt_raw = jnp.split(bcd, [d_state, 2 * d_state], axis=-1)
        dt = jax.nn.softplus(dt_raw + params["dt_bias"])  # [B,S,nh]
        A = -jnp.exp(params["A_log"])  # [nh]
        da = jnp.exp(dt.astype(jnp.float32) * A)[..., None, None]  # [B,S,nh,1,1]
        xh = x.reshape(B, S, nh, head_p)
        dbx = (dt[..., None] * xh)[..., None] * Bc[:, :, None, None, :]
        hs, state = _scan_states(da, dbx, state0, chunk)
        y = jnp.einsum("bsnpN,bsN->bsnp", hs, Cc.astype(jnp.float32))
        y = y.reshape(B, S, d_inner)
    y = y.astype(u.dtype) + params["D"] * x.reshape(B, S, d_inner)
    # gated RMSNorm (Mamba-2 style; harmless for mamba1)
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(var + 1e-6) * (1.0 + params["norm"].astype(jnp.float32))
    out = yf.astype(u.dtype) @ params["out_proj"]

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "ssm": state}
    return out, new_cache


def init_mamba_cache(
    batch: int, d_inner: int, d_state: int, conv_width: int, variant: str, dtype,
    head_p: int = 64,
) -> Dict:
    nh = d_inner if variant == "mamba1" else d_inner // head_p
    p_dim = 1 if variant == "mamba1" else head_p
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "ssm": jnp.zeros((batch, nh, p_dim, d_state), jnp.float32),
    }
