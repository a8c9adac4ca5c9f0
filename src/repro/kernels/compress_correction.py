"""Fused compressed-correction kernel: select + quantize + error feedback.

The `CompressedGT` / `QuantizedGT` strategies transform each tracking
correction leaf (shape [agents, ...], flattened to [R, C]) three ways per
round: inject the error-feedback residual (ceff = c + e), keep the k
largest-magnitude (or a random-k subset of) entries, stochastically
quantize the kept values to `bits` bits with a per-row scale, and write
the dropped mass back into the feedback buffer (e' = ceff - chat).  Done
naively that is four elementwise passes plus a dense mask over HBM; this
kernel streams c, e and the two uniform arrays through VMEM once and
writes both outputs fused (mirroring `kernels/gt_update.py` for the
dense update).

The grid tiles rows only — per-row top-k and the per-row quantization
scale need the full C-length row resident in VMEM, so C must be
lane-aligned (C % 128 == 0) and one (block_rows, C) tile must fit VMEM;
correction leaves are (num_agents, prod(param_shape)) so R is small.
Pallas has no TPU lowering for the `jax.lax.top_k` of the exact-k
selection, so on a TPU only the quantize-only configuration (k == C)
compiles; a selecting one is refused by name (`backend.
refuse_tpu_lowering`).  Off a TPU the kernel runs in the interpreter.

Selection and rounding randomness comes in as iid U[0,1) inputs rather
than an in-kernel PRNG: keeping the k largest uniforms IS a uniform
k-subset (rand-k), `floor(u) + [u_rnd < frac(u)]` IS unbiased stochastic
rounding, and sharing the draws with the pure-jnp oracle
(`ref.compress_correction_ref`) makes kernel-vs-reference and
kernel-vs-fallback comparisons exact instead of distributional.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .backend import refuse_tpu_lowering, resolve_interpret

LANE = 128  # TPU lane width: last dim of every tile must be a multiple
_NO_TPU_SELECT = "Pallas TPU has no lowering for top_k (the exact-k selection)"


def _compress_kernel(c_ref, e_ref, us_ref, ur_ref, chat_ref, res_ref, *,
                     k: int, bits: int, mode: str, has_feedback: bool):
    ct = ref.compute_dtype(c_ref.dtype)
    ceff = c_ref[...].astype(ct)
    if has_feedback:
        ceff = ceff + e_ref[...].astype(ct)
    n = ceff.shape[-1]
    if k < n:
        score = jnp.abs(ceff) if mode == "topk" else us_ref[...].astype(ct)
        kept = jnp.where(ref.exact_k_mask(score, k), ceff, jnp.zeros_like(ceff))
    else:
        kept = ceff
    if bits < 32:
        chat = ref.stochastic_quantize(kept, ur_ref[...], bits, ct)
    else:
        chat = kept
    chat_ref[...] = chat.astype(chat_ref.dtype)
    # residual against the DELIVERED (dtype-cast) values, so the feedback
    # buffer absorbs the storage-dtype rounding too
    res_ref[...] = (ceff - chat_ref[...].astype(ct)).astype(res_ref.dtype)


def _row_block(R: int, want: int) -> int:
    br = max(1, min(want, R))
    while R % br:
        br -= 1
    return br


def _operand(arr, dtype, spec):
    """Stand-in for an unused kernel operand: the python-level gates in
    the kernel bodies are trace-time constants, so a None operand is
    never read — but pallas_call arity is fixed, so substitute one
    (1, LANE) tile pinned to block (0, 0) so nothing dense is
    materialized or streamed through VMEM.  Shared by every kernel in
    this package that takes optional uniforms/feedback inputs."""
    if arr is None:
        return jnp.zeros((1, LANE), dtype), pl.BlockSpec(
            (1, LANE), lambda i: (0, 0)
        )
    return arr, spec


def compress_correction_2d(
    c: jax.Array,  # [R, C], C % 128 == 0
    e: Optional[jax.Array],  # [R, C] feedback residual, or None
    u_sel: Optional[jax.Array],  # [R, C] U[0,1) — rand-k scores (randk only)
    u_rnd: Optional[jax.Array],  # [R, C] U[0,1) — stochastic rounding (bits<32)
    *,
    k: int,
    bits: int = 32,
    mode: str = "topk",
    block_rows: int = 8,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One fused VMEM pass of (feedback-inject, exact-k select, quantize,
    residual-update) per row.  Returns (chat, resid), both c.dtype and
    bitwise-equal to `ref.compress_correction_ref` on the same inputs."""
    R, C = c.shape
    assert C % LANE == 0, f"fused path needs lane-aligned leaves, got C={C}"
    assert mode in ("topk", "randk"), mode
    interpret = resolve_interpret(interpret)
    if not interpret and k < C:
        refuse_tpu_lowering("compress_correction_2d", _NO_TPU_SELECT)
    if bits < 32:
        assert u_rnd is not None, "stochastic rounding (bits<32) needs u_rnd"
    if mode == "randk" and k < C:
        assert u_sel is not None, "rand-k selection needs u_sel scores"
    br = _row_block(R, block_rows)
    spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    has_feedback = e is not None
    e_arr, e_spec = _operand(e, c.dtype, spec)
    us_arr, us_spec = _operand(u_sel, c.dtype, spec)
    ur_arr, ur_spec = _operand(u_rnd, c.dtype, spec)
    kern = functools.partial(
        _compress_kernel, k=k, bits=bits, mode=mode, has_feedback=has_feedback
    )
    return pl.pallas_call(
        kern,
        grid=(R // br,),
        in_specs=[spec, e_spec, us_spec, ur_spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
        ),
        interpret=interpret,
    )(c, e_arr, us_arr, ur_arr)


def fusable_leaf(flat: jax.Array) -> bool:
    """The fused kernel handles 2D leaves with a lane-aligned row length."""
    return flat.ndim == 2 and flat.shape[-1] > 0 and flat.shape[-1] % LANE == 0


def compress_leaf(
    c: jax.Array,
    e: Optional[jax.Array],
    u_sel: Optional[jax.Array],
    u_rnd: Optional[jax.Array],
    *,
    k: int,
    bits: int = 32,
    mode: str = "topk",
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Strategy-facing dispatcher: the fused Pallas path on aligned 2D
    leaves, the pure-jnp oracle otherwise.  Both paths are the same math
    on the same uniforms — the choice moves results by at most the last
    ulp (the kernel compiles as one XLA unit whose fusion may round
    differently than the per-op path)."""
    if use_kernel and fusable_leaf(c):
        return compress_correction_2d(
            c, e, u_sel, u_rnd, k=k, bits=bits, mode=mode
        )
    return ref.compress_correction_ref(c, e, u_sel, u_rnd, k=k, bits=bits, mode=mode)
