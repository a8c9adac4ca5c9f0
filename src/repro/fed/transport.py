"""Sparse wire transport for compressed tracking corrections.

PR 1/PR 2 priced the compressed-correction exchange analytically
(`CommStrategy.bytes_per_round`) but still moved DENSE masked tensors:
the fused compress kernel hands the engine a dense tree, so the traffic
the collectives carry never matched the price.  This module is the wire
format that closes that gap:

  LeafSpec      static layout of one packed leaf — rows (quantization
                groups), cols, kept-per-row k, bits, the chosen encoding
                and the index/scale widths.  `LeafSpec.build` is the
                SINGLE owner of the payload arithmetic: the strategies'
                `bytes_per_round` pricing and the encoder's buffer
                shapes both derive from it, so priced bytes equal packed
                buffer lengths by construction.
  LeafPayload   the actual packed buffers for one leaf: bit-packed
                uint32 words (or raw values), uint16/int32 indices, and
                per-row scales in a CSR-style flat layout (k is constant
                per row, so offsets are implicit).
  encode_leaf / decode_leaf
                pack one flattened [R, C] leaf / scatter-add it back to
                the dense correction; fused Pallas path on lane-aligned
                leaves, pure-jnp oracle otherwise (both are
                `kernels.ref.pack_payload_ref`'s math on the same
                uniform draws, so decode(encode(c)) reproduces the dense
                compressed correction bitwise).
  PackedTree    what a wire-transport strategy returns from
                `transform_correction` instead of a dense tree; the
                engine's server aggregation path calls `.decode()` to
                scatter-add the payloads back before the local steps.
  measured_bytes_per_round
                probe of the ACTUAL packed buffer lengths (via
                jax.eval_shape over the encoder), reported next to the
                analytic price in `fed.comm.comm_table` and
                benchmarks/comm_efficiency.py so the accounting cannot
                silently drift.

Quantization groups are the rows of the [R, C] layout: a per-agent leaf
of shape (.., d) contributes size // d rows of length d (vectors are one
row), each with its own max-abs scale — and the pricing charges one
scale per GROUP, not one per leaf.  Index width derives from the row
length (uint16 up to 2**16 columns, int32 beyond).  Values are stored at
`ref.storage_bits(bits)` — the next power-of-two sub-word width — so
levels never straddle words.  Each packed leaf also carries a fixed
HEADER_BYTES of static metadata (rows/cols/k/bits/encoding/dtype tags),
priced separately from the payload.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ref
from ..kernels.compress_correction import LANE
from ..kernels.pack_payload import pack_payload_2d, unpack_payload_2d

Pytree = Any

#: fixed per-leaf wire header: rows (u32) + cols (u32) + k (u32) +
#: bits/mode/encoding/index-width/scale-width/dtype tags (4 bytes)
HEADER_BYTES = 16


def wire_rows_cols(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """[rows, cols] wire layout of one per-agent leaf: last-axis rows are
    the quantization groups (per-channel scales for matrices), vectors
    and scalars are a single group."""
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return 1, max(1, shape[0])
    cols = shape[-1]
    return int(np.prod(shape[:-1], dtype=np.int64)), cols


def index_dtype_for(cols: int):
    """Narrowest integer that can index a row of length `cols` (max
    stored index cols - 1) — the same width the pricing charges (no
    hard-coded 4-byte indices).  UNSIGNED 16-bit, not int16: column
    indices reach cols - 1, and a signed halfword overflows at 2**15,
    silently corrupting the scatter-add for rows between 32769 and
    65536 columns."""
    return jnp.uint16 if cols <= 2**16 else jnp.int32


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static wire layout of one packed correction leaf."""

    rows: int
    cols: int
    k: int            # kept entries per row (== cols when not sparsifying)
    bits: int         # quantization grid width (>= 32: unquantized)
    mode: str         # "topk" | "randk" (does not affect bytes)
    dtype: Any        # leaf value dtype (np.dtype)
    #: wire representation, the cheapest of:
    #:   dense        the full masked/quantized row at leaf dtype
    #:   sparse       k (value, index) pairs at leaf dtype
    #:   quant        k bit-packed levels + indices + per-row scale
    #:   quant_dense  ALL cols bit-packed levels + per-row scale, no
    #:                indices (masked levels encode exact zeros) — wins
    #:                over `quant` once k/cols outgrows the index cost
    encoding: str

    @classmethod
    def build(cls, shape, dtype, ratio: float, bits: int,
              mode: str = "topk") -> "LeafSpec":
        """Layout for one per-agent leaf of `shape`/`dtype` compressed at
        (`ratio`, `bits`): picks the cheapest encoding, exactly like the
        payload pricing (this IS the payload pricing) — candidate costs
        are wire_bytes() itself, so the chooser and the buffers cannot
        desynchronize.

        The encoding only chooses the wire REPRESENTATION of the
        already-compressed values — `bits` < 32 quantizes every leaf of
        the tree uniformly (the estimator the convergence analysis sees
        must not vary with leaf size), so a tiny leaf whose cheapest
        encoding is "sparse" or "dense" still carries quantized values,
        just at full storage width."""
        rows, cols = wire_rows_cols(tuple(shape))
        dt = np.dtype(dtype)
        k = cols if ratio >= 1 else max(1, math.ceil(ratio * cols))
        candidates = ["dense"]
        if k < cols:
            candidates.append("sparse")
        if bits < 32:
            candidates.append("quant")
            if k < cols:
                candidates.append("quant_dense")
        base = cls(rows, cols, k, bits, mode, dt, "dense")
        costs = {
            e: dataclasses.replace(base, encoding=e).wire_bytes()
            for e in candidates
        }
        encoding = min(costs, key=lambda e: (costs[e], e != "dense"))
        return dataclasses.replace(base, encoding=encoding)

    def stacked(self, m: int) -> "LeafSpec":
        """The same layout with m agents' rows stacked (the shape the
        strategies actually encode); costs scale linearly, so the
        encoding choice is unchanged."""
        return dataclasses.replace(self, rows=self.rows * m)

    # ------------------------------------------------------ wire widths
    @property
    def sparse(self) -> bool:
        return self.k < self.cols

    @property
    def index_dtype(self):
        return index_dtype_for(self.cols)

    @property
    def scale_dtype(self):
        return ref.compute_dtype(self.dtype)

    @property
    def words_per_row(self) -> int:
        n = self.cols if self.encoding == "quant_dense" else self.k
        return ref.word_layout(n, self.bits)[2]

    def wire_bytes(self) -> int:
        """Exact payload bytes of the packed buffers (no header) — the
        single owner of the per-encoding arithmetic: LeafSpec.build's
        chooser and LeafPayload.nbytes both reduce to it."""
        if self.encoding == "dense":
            return self.rows * self.cols * self.dtype.itemsize
        idx = self.rows * self.k * np.dtype(self.index_dtype).itemsize
        if self.encoding == "sparse":
            return self.rows * self.k * self.dtype.itemsize + idx
        scale = self.rows * np.dtype(self.scale_dtype).itemsize
        words = self.rows * 4 * self.words_per_row
        if self.encoding == "quant_dense":
            return words + scale
        return words + scale + (idx if self.sparse else 0)

    def total_bytes(self) -> int:
        return self.wire_bytes() + HEADER_BYTES


class LeafPayload(NamedTuple):
    """Packed buffers of one leaf.  indices is None for dense encodings
    (and for k == cols, where indices are implicit); scales is None
    unless the values are bit-packed quantized levels."""

    data: jax.Array
    indices: Optional[jax.Array]
    scales: Optional[jax.Array]

    @property
    def nbytes(self) -> int:
        return sum(
            int(a.size) * np.dtype(a.dtype).itemsize
            for a in (self.data, self.indices, self.scales)
            if a is not None
        )


def _fusable(spec: LeafSpec) -> bool:
    return spec.cols > 0 and spec.cols % LANE == 0


def encode_leaf(
    c: jax.Array,  # [rows, cols] flattened leaf (feedback NOT yet injected)
    e: Optional[jax.Array],
    u_sel: Optional[jax.Array],
    u_rnd: Optional[jax.Array],
    spec: LeafSpec,
    *,
    use_kernel: bool = False,
) -> Tuple[LeafPayload, jax.Array]:
    """Pack one leaf into its wire payload.  Returns (payload, resid)
    with resid = (c + e) - decode(payload) in c.dtype — the
    error-feedback update, identical to the dense compress path's."""
    kw = dict(
        k=spec.k, bits=spec.bits, mode=spec.mode, encoding=spec.encoding
    )
    if use_kernel and _fusable(spec):
        data, idx, scale, resid = pack_payload_2d(
            c, e, u_sel, u_rnd,
            index_dtype=spec.index_dtype, scale_dtype=spec.scale_dtype, **kw
        )
    else:
        data, idx, scale, resid = ref.pack_payload_ref(
            c, e, u_sel, u_rnd, index_dtype=spec.index_dtype, **kw
        )
    keep_idx = spec.sparse and spec.encoding in ("sparse", "quant")
    keep_scale = spec.encoding in ("quant", "quant_dense")
    return (
        LeafPayload(data, idx if keep_idx else None,
                    scale if keep_scale else None),
        resid,
    )


def decode_leaf(
    payload: LeafPayload,
    spec: LeafSpec,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Scatter-add the packed payload back to the dense [rows, cols]
    compressed correction (bitwise the chat that produced it)."""
    rows = payload.data.shape[0]
    idx = payload.indices
    if idx is None:  # dense, or k == cols: indices are implicit
        idx = jax.lax.broadcasted_iota(jnp.int32, (rows, spec.k), 1)
    scale = payload.scales
    if scale is None:
        scale = jnp.zeros((rows, 1), spec.scale_dtype)
    kw = dict(
        cols=spec.cols, dtype=spec.dtype, k=spec.k, bits=spec.bits,
        encoding=spec.encoding,
    )
    if use_kernel and _fusable(spec):
        return unpack_payload_2d(payload.data, idx, scale, **kw)
    return ref.decode_payload_ref(payload.data, idx, scale, **kw)


class PackedTree:
    """A correction pytree in wire format: what a wire-transport strategy
    returns from `transform_correction` instead of the dense tree.  The
    engine's server aggregation path detects it by its `decode` hook and
    scatter-adds the payloads back into dense [m, *leaf_shape] arrays
    before driving the local steps."""

    def __init__(self, payloads: List[LeafPayload], specs: List[LeafSpec],
                 treedef, shapes: List[Tuple[int, ...]],
                 use_kernel: bool = False):
        self.payloads = payloads
        self.specs = specs
        self.treedef = treedef
        self.shapes = shapes  # original [m, *leaf_shape] shapes
        self.use_kernel = use_kernel

    def decode(self) -> Pytree:
        leaves = [
            decode_leaf(p, s, use_kernel=self.use_kernel).reshape(shape)
            for p, s, shape in zip(self.payloads, self.specs, self.shapes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    def wire_bytes(self) -> int:
        """Actual packed buffer bytes across all leaves and agents."""
        return sum(p.nbytes for p in self.payloads)

    def total_bytes(self) -> int:
        return self.wire_bytes() + HEADER_BYTES * len(self.payloads)


# --------------------------------------------------------------------------
# measured-bytes probe (actual packed buffer lengths, not the price)
# --------------------------------------------------------------------------
def probe_leaf_bytes(spec: LeafSpec) -> int:
    """Measure one leaf's payload by ENCODING it abstractly: eval_shape
    the encoder and sum the emitted buffer sizes.  This is the empirical
    check on LeafSpec.wire_bytes — the two must agree (and a conformance
    test pins that), but the probe never trusts the arithmetic."""
    c = jax.ShapeDtypeStruct((spec.rows, spec.cols), spec.dtype)
    u = jax.ShapeDtypeStruct((spec.rows, spec.cols), jnp.float32)
    payload = jax.eval_shape(
        lambda cc, uu: encode_leaf(cc, None, uu, uu, spec)[0], c, u
    )
    return sum(
        int(s.size) * np.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(payload)
    )


def dense_payload_bytes(tree: Pytree) -> int:
    """Dense payload bytes of one model copy (works on arrays and
    ShapeDtypeStructs alike) — the single owner of the dense-size sum,
    shared with the strategies' pricing."""
    return sum(
        int(u.size) * np.dtype(u.dtype).itemsize for u in jax.tree.leaves(tree)
    )


def measured_bytes_per_round(
    strategy, x: Pytree, y: Pytree, num_local_steps: int,
    *, include_headers: bool = True,
) -> int:
    """Per-agent wire bytes of one round, MEASURED from the packed buffer
    shapes the encoder actually emits (plus HEADER_BYTES per compressed
    leaf per direction unless disabled).  For strategies that exchange
    dense tensors only (full sync, local-only, plain gradient tracking)
    the wire format is the tensors themselves, so the measurement is the
    analytic `bytes_per_round` — and a compressor with wire_transport
    OFF also moves dense masked corrections, so it measures at the dense
    gradient-tracking cost, not at its price: the gap between the two
    columns is exactly what enabling the wire buys."""
    ratio = getattr(strategy, "_ratio", 1.0)
    bits = getattr(strategy, "_bits", 32)
    if ratio >= 1 and bits >= 32:
        return int(strategy.bytes_per_round(x, y, num_local_steps))
    # the engine casts corrections to correction_dtype before the
    # transform, so that — not the model dtype — is what actually moves
    cdt = getattr(strategy, "correction_dtype", None)
    if not getattr(strategy, "wire_transport", False):
        # dense masked corrections actually move: up grad + model, down
        # global grad + model — corrections at the correction dtype
        corr = dense_payload_bytes(
            jax.tree.map(
                lambda u: jax.ShapeDtypeStruct(u.shape, cdt or u.dtype),
                (x, y),
            )
        )
        return 2 * dense_payload_bytes((x, y)) + 2 * corr
    mode = getattr(strategy, "mode", "topk")
    leaves = jax.tree.leaves((x, y))
    payload = header = 0
    for u in leaves:
        spec = LeafSpec.build(u.shape, cdt or u.dtype, ratio, bits, mode)
        payload += probe_leaf_bytes(spec)
        header += HEADER_BYTES
    # up: compressed correction + dense local model; down: compressed
    # global correction + dense averaged model — mirroring bytes_per_round
    total = 2 * dense_payload_bytes((x, y)) + 2 * payload
    if include_headers:
        total += 2 * header
    return int(total)


def wire_header_overhead(x: Pytree, y: Pytree) -> int:
    """Fixed per-round header bytes: HEADER_BYTES per leaf per direction
    — the documented gap between measured and priced bytes."""
    return 2 * HEADER_BYTES * len(jax.tree.leaves((x, y)))
