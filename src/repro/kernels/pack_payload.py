"""Fused wire-payload kernels: select + quantize + bit-pack in one pass.

`compress_correction.py` (PR 2) fuses the compressed-correction MATH but
still materializes a dense masked tree, so the collectives move dense
tensors and `bytes_per_round` prices traffic that never happens.  These
kernels produce (and consume) the actual packed wire format of
`repro.fed.transport`:

  pack_payload_2d    ceff [R, C] -> (data, idx, scale, resid): feedback
                     injection, exact-k selection, QSGD quantization,
                     index extraction and uint32 bit-packing fused in one
                     VMEM pass per row block (the residual never leaves
                     VMEM between the select and the pack);
  unpack_payload_2d  (data, idx, scale) -> dense chat [R, C]: word
                     unpack, dequantization and the scatter-add back to
                     the dense correction, one VMEM pass.

The grid tiles rows only, like compress_correction: per-row top-k, the
per-row quantization scale and the per-row index extraction all need the
full C-length row resident in VMEM, so the fused path requires
lane-aligned leaves (C % 128 == 0).  On a TPU only the dense encoding
lowers: Pallas has no TPU lowering for the exact-k selection (`top_k`),
the uint32 bit-packing (a reduction over unsigned integers), the
lane gather of kept values or the scatter-add of the decode, and a
configuration that needs one is refused by name
(`backend.refuse_tpu_lowering`; the all-levels unpack lowers but
overflows VMEM at real leaf widths).  The kernel bodies ARE the oracles —
each invokes `ref.pack_payload_ref` / `ref.decode_payload_ref` on its
VMEM-resident block, so kernel == oracle by construction on the same
uniform draws: data and idx agree BITWISE, values to <= 1 ulp (the
kernel compiles as one XLA unit whose fusion may round differently).

Like compress_correction, randomness arrives as iid U[0,1) inputs rather
than an in-kernel PRNG so the kernel, the pure-jnp oracle and the
strategy fallback can be compared exactly instead of distributionally.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .backend import refuse_tpu_lowering, resolve_interpret
from .compress_correction import LANE, _NO_TPU_SELECT, _operand, _row_block

# why each non-dense encoding is refused on a TPU (module docstring)
_NO_TPU_PACK = {
    "quant": "Pallas TPU has no lowering for the uint32 bit-packing sum",
    "quant_dense": "Pallas TPU has no lowering for the uint32 bit-packing sum",
    "sparse": "Pallas TPU has no lowering for the lane gather of kept values",
}
_NO_TPU_UNPACK = {
    "quant": "Pallas TPU has no lowering for scatter-add",
    "sparse": "Pallas TPU has no lowering for scatter-add",
    "quant_dense": "its word unpack overflows VMEM at real leaf widths",
}


def _pack_kernel(c_ref, e_ref, us_ref, ur_ref,
                 data_ref, idx_ref, scale_ref, res_ref, *,
                 k: int, bits: int, mode: str, encoding: str,
                 has_feedback: bool, needs_sel: bool):
    # the oracle IS the kernel body — one implementation of the encode
    # math, so kernel == oracle by construction (not by transcription);
    # unused operands are trace-time None so the dummy tiles are never
    # read
    data, idx, scale, resid = ref.pack_payload_ref(
        c_ref[...],
        e_ref[...] if has_feedback else None,
        us_ref[...] if needs_sel else None,
        ur_ref[...] if bits < 32 else None,
        k=k, bits=bits, mode=mode, encoding=encoding,
        index_dtype=idx_ref.dtype,
    )
    data_ref[...] = data
    idx_ref[...] = idx
    scale_ref[...] = scale.astype(scale_ref.dtype)
    res_ref[...] = resid


def pack_payload_2d(
    c: jax.Array,  # [R, C], C % 128 == 0
    e: Optional[jax.Array],  # [R, C] feedback residual, or None
    u_sel: Optional[jax.Array],  # [R, C] U[0,1) — rand-k scores
    u_rnd: Optional[jax.Array],  # [R, C] U[0,1) — stochastic rounding
    *,
    k: int,
    bits: int = 32,
    mode: str = "topk",
    encoding: str = "quant",
    index_dtype=jnp.int32,
    scale_dtype=None,
    block_rows: int = 8,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused VMEM pass of (feedback-inject, exact-k select, quantize,
    index-extract, bit-pack, residual-update) per row block.  Returns
    (data, idx, scale, resid) exactly like `ref.pack_payload_ref` —
    data/idx bitwise-equal, scale/resid to the last ulp."""
    R, C = c.shape
    assert C % LANE == 0, f"fused path needs lane-aligned leaves, got C={C}"
    assert mode in ("topk", "randk"), mode
    assert encoding in ("quant", "quant_dense", "sparse", "dense"), encoding
    if bits < 32:
        assert u_rnd is not None, "stochastic rounding (bits<32) needs u_rnd"
    else:
        assert encoding not in ("quant", "quant_dense"), (
            "bit-packing needs bits < 32"
        )
    if mode == "randk" and k < C:
        assert u_sel is not None, "rand-k selection needs u_sel scores"
    interpret = resolve_interpret(interpret)
    if not interpret and k < C:
        refuse_tpu_lowering("pack_payload_2d", _NO_TPU_SELECT)
    if not interpret and encoding != "dense":
        refuse_tpu_lowering("pack_payload_2d", _NO_TPU_PACK[encoding])
    if encoding in ("quant", "quant_dense"):
        n = C if encoding == "quant_dense" else k
        data_shape, data_dtype = (R, ref.word_layout(n, bits)[2]), jnp.uint32
    elif encoding == "sparse":
        data_shape, data_dtype = (R, k), c.dtype
    else:
        data_shape, data_dtype = (R, C), c.dtype
    scale_dtype = scale_dtype or ref.compute_dtype(c.dtype)
    br = _row_block(R, block_rows)
    spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    e_arr, e_spec = _operand(e, c.dtype, spec)
    us_arr, us_spec = _operand(u_sel, c.dtype, spec)
    ur_arr, ur_spec = _operand(u_rnd, c.dtype, spec)
    kern = functools.partial(
        _pack_kernel, k=k, bits=bits, mode=mode, encoding=encoding,
        has_feedback=e is not None,
        needs_sel=mode == "randk" and k < C,
    )
    row_spec = lambda w: pl.BlockSpec((br, w), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(R // br,),
        in_specs=[spec, e_spec, us_spec, ur_spec],
        out_specs=(row_spec(data_shape[1]), row_spec(k), row_spec(1), spec),
        out_shape=(
            jax.ShapeDtypeStruct(data_shape, data_dtype),
            jax.ShapeDtypeStruct((R, k), index_dtype),
            jax.ShapeDtypeStruct((R, 1), scale_dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
        ),
        interpret=interpret,
    )(c, e_arr, us_arr, ur_arr)


def _unpack_kernel(data_ref, idx_ref, scale_ref, out_ref, *,
                   k: int, bits: int, encoding: str, cols: int):
    # the oracle IS the kernel body — one implementation of the decode
    # math, so kernel == oracle by construction (not by transcription)
    out_ref[...] = ref.decode_payload_ref(
        data_ref[...], idx_ref[...], scale_ref[...],
        cols=cols, dtype=out_ref.dtype, k=k, bits=bits, encoding=encoding,
    )


def unpack_payload_2d(
    data: jax.Array,
    idx: jax.Array,
    scale: jax.Array,
    *,
    cols: int,
    dtype,
    k: int,
    bits: int = 32,
    encoding: str = "quant",
    block_rows: int = 8,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused unpack + dequantize + scatter-add back to the dense [R, cols]
    compressed correction; bitwise-equal to `ref.decode_payload_ref`."""
    assert cols % LANE == 0, f"fused path needs lane-aligned leaves, got {cols}"
    assert encoding in ("quant", "quant_dense", "sparse", "dense"), encoding
    interpret = resolve_interpret(interpret)
    if not interpret and encoding != "dense":
        refuse_tpu_lowering("unpack_payload_2d", _NO_TPU_UNPACK[encoding])
    R = data.shape[0]
    br = _row_block(R, block_rows)
    row_spec = lambda w: pl.BlockSpec((br, w), lambda i: (i, 0))
    kern = functools.partial(
        _unpack_kernel, k=k, bits=bits, encoding=encoding, cols=cols
    )
    return pl.pallas_call(
        kern,
        grid=(R // br,),
        in_specs=[row_spec(data.shape[1]), row_spec(idx.shape[1]),
                  row_spec(scale.shape[1])],
        out_specs=row_spec(cols),
        out_shape=jax.ShapeDtypeStruct((R, cols), dtype),
        interpret=interpret,
    )(data, idx, scale)
