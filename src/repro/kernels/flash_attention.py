"""Blocked online-softmax (flash) attention for TPU.

Grid (batch*heads, q_blocks, kv_blocks); the KV axis is the innermost,
sequentially-iterated dimension so the running (max, denom, acc) scratch
persists across KV tiles in VMEM.  Q tiles stay resident; K/V stream in
(block_kv, head_dim) tiles.  Supports causal masking, sliding windows and
the Gemma-2 logit softcap.  MXU-aligned tiles (multiples of 128 on the
seq axes; head_dim padded by the caller if needed).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, causal, window, softcap, block_q, block_kv, num_kv_blocks,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= q_pos - k_pos < window

    # skip fully-masked tiles (above the causal diagonal / outside the window)
    needed = jnp.logical_not(causal) | (
        (iq + 1) * block_q - 1 >= ik * block_kv
    )
    if window > 0:
        needed &= iq * block_q < ik * block_kv + block_kv - 1 + window

    @pl.when(needed)
    def _tile():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))
        ) * sm_scale  # [bq, bkv]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, H, Sq, hd]
    k: jax.Array,  # [B, H, Skv, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nk = Sq // block_q, Skv // block_kv
    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * H, Skv, hd)
    vf = v.reshape(B * H, Skv, hd)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=1.0 / (hd**0.5),
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)
