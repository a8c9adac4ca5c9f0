"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gt_update_ref(z, g, c, eta: float, sign: float):
    """Fused FedGDA-GT inner update: z + sign*eta*(g + c)."""
    return z + sign * eta * (g + c.astype(g.dtype))


def compute_dtype(dtype):
    """f64 in, f64 math; anything narrower (f32/bf16/f16/float8) runs in
    f32.  Explicit because jnp.promote_types has no implicit promotion
    path for the float8 correction dtypes."""
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def quantize_levels(kept, u_rnd, bits: int, ct):
    """QSGD quantization half: map each row of `kept` onto the symmetric
    s = 2^(bits-1)-1 grid with a per-row max-abs scale and round
    STOCHASTICALLY (floor + Bernoulli(frac)) — unbiased given
    u_rnd ~ U[0,1).  Returns (q, scale): integer-valued grid levels in
    [-s, s] (carried in the compute dtype) and the per-row scale.  The
    wire transport stores exactly (q + s, scale), so this function is
    the single owner of the level math for the dense path, the pack
    kernel, and the packed encoder alike."""
    s = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(kept), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
    u = kept * (s / safe)
    lo = jnp.floor(u)
    q = lo + (u_rnd.astype(ct) < u - lo).astype(ct)
    # fp rounding can land u an ulp outside [-s, s] (|kept| == scale with
    # s/safe rounded up), making floor/ceil reach -s-1 or s+1; -s-1 would
    # wrap to 0xFFFFFFFF as a packed level and corrupt every neighbour in
    # its uint32 word, so clamp to the grid in the ONE shared quantizer —
    # dense path, fused kernels and wire codec stay bitwise-identical
    return jnp.clip(q, -s, s), scale


def dequantize_levels(q, scale, bits: int, ct):
    """QSGD dequantization half: q * scale / s, written as a
    constant-reciprocal multiply, not q*(safe/s): XLA compiles the
    division differently inside vs outside the interpret-mode kernel
    (1 f32 ulp), enough to flip a bf16 rounding boundary — sharing one
    implementation keeps kernel == oracle == wire decode bitwise."""
    s = float(2 ** (bits - 1) - 1)
    safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
    return q * (safe * (1.0 / s))


def stochastic_quantize(kept, u_rnd, bits: int, ct):
    """QSGD core shared VERBATIM by the oracle and the Pallas kernels
    (`compress_correction._compress_kernel` calls this inside the kernel
    body): quantize_levels . dequantize_levels, so the dense compressed
    correction and the decoded wire payload are the same bits."""
    q, scale = quantize_levels(kept, u_rnd, bits, ct)
    return dequantize_levels(q, scale, bits, ct)


def exact_k_mask(score, k: int):
    """Boolean mask keeping exactly k entries per row of `score` [R, C]:
    the k largest, earliest index winning ties (the `jax.lax.top_k`
    order, so a >=threshold mask can never degenerate to dense when the
    k-th score is tied or zero)."""
    n = score.shape[-1]
    if k >= n:
        return jnp.ones(score.shape, bool)
    thr = jax.lax.top_k(score, k)[0][..., -1:]
    gt = score > thr
    n_gt = jnp.sum(gt, axis=-1, keepdims=True)
    tie = score == thr
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
    return gt | (tie & (tie_rank <= k - n_gt))


def compress_correction_ref(c, e, u_sel, u_rnd, *, k: int, bits: int,
                            mode: str = "topk"):
    """Oracle of the fused compress-correction kernel on one flattened
    leaf c [R, C] (R = agents): error-feedback injection, exact-k
    selection, QSGD stochastic quantization, residual update.

      ceff = c + e                         (e may be None)
      kept = ceff * exact_k_mask(score)    score = |ceff| (topk) | u_sel (randk)
      chat = round_stoch(kept/scale * s) * scale/s   per-row scale = max|kept|,
                                           s = 2^(bits-1)-1; identity for bits>=32
      resid = ceff - chat                  (what compression+quantization dropped)

    u_sel / u_rnd are iid U[0,1) arrays of c's shape (keeping the k largest
    uniforms IS a uniform k-subset; round_stoch(u) = floor(u) + [u_rnd < frac]).
    Returns (chat, resid), both in c.dtype.  Math runs in
    `compute_dtype(c.dtype)` exactly like the kernel."""
    ct = compute_dtype(c.dtype)
    ceff = c.astype(ct) if e is None else c.astype(ct) + e.astype(ct)
    n = ceff.shape[-1]
    if k < n:
        score = jnp.abs(ceff) if mode == "topk" else u_sel.astype(ct)
        kept = jnp.where(exact_k_mask(score, k), ceff, jnp.zeros_like(ceff))
    else:
        kept = ceff
    if bits < 32:
        chat = stochastic_quantize(kept, u_rnd, bits, ct)
    else:
        chat = kept
    chat = chat.astype(c.dtype)
    resid = (ceff - chat.astype(ct)).astype(c.dtype)
    return chat, resid


# ----------------------------------------------------------------------
# packed (value, index) wire payloads — oracles of kernels/pack_payload.py
# ----------------------------------------------------------------------
_WORD_BITS = 32
_STORAGE_WIDTHS = (2, 4, 8, 16, 32)


def storage_bits(bits: int) -> int:
    """Wire width of one quantized level: the smallest power-of-two
    sub-word width (2/4/8/16/32) holding `bits` bits, so levels never
    straddle a uint32 word boundary and packing stays a vectorized
    shift+sum.  The payload pricing uses the same function, so priced
    and packed widths agree by construction."""
    for w in _STORAGE_WIDTHS:
        if w >= bits:
            return w
    raise ValueError(f"bits={bits} exceeds the 32-bit word")


def word_layout(k: int, bits: int):
    """(storage bits, levels per uint32 word, words per row) for k kept
    levels of `bits`-bit quantized values."""
    sb = storage_bits(bits)
    per_word = _WORD_BITS // sb
    return sb, per_word, -(-k // per_word)


def kept_indices(mask, k: int):
    """Column indices [.., k] (ascending, int32) of the k True entries
    per row of `mask` — the index half of a packed sparse payload.
    Kept columns sort below C + anything, so one jnp.sort suffices."""
    C = mask.shape[-1]
    it = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    return jnp.sort(jnp.where(mask, it, it + C), axis=-1)[..., :k]


def pack_words(levels, bits: int):
    """Bit-pack non-negative integer levels [.., k] (uint32, each <
    2^storage_bits) into uint32 words [.., W], level i of a row landing
    at bit (i % per_word) * storage_bits of word i // per_word."""
    k = levels.shape[-1]
    sb, per_word, W = word_layout(k, bits)
    pad = [(0, 0)] * (levels.ndim - 1) + [(0, W * per_word - k)]
    lv = jnp.pad(levels, pad).reshape(*levels.shape[:-1], W, per_word)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, lv.shape, lv.ndim - 1)
    return jnp.sum(
        jnp.left_shift(lv, shifts * jnp.uint32(sb)),
        axis=-1,
        dtype=jnp.uint32,  # disjoint bit ranges: sum == bitwise or
    )


def unpack_words(words, k: int, bits: int):
    """Inverse of pack_words: uint32 words [.., W] -> levels [.., k]."""
    sb, per_word, W = word_layout(k, bits)
    lv = jnp.broadcast_to(
        words[..., None], (*words.shape, per_word)
    )
    shifts = jax.lax.broadcasted_iota(jnp.uint32, lv.shape, lv.ndim - 1)
    lv = jnp.right_shift(lv, shifts * jnp.uint32(sb)) & jnp.uint32(2**sb - 1)
    return lv.reshape(*words.shape[:-1], W * per_word)[..., :k]


def pack_payload_ref(c, e, u_sel, u_rnd, *, k: int, bits: int,
                     mode: str = "topk", encoding: str = "quant",
                     index_dtype=jnp.int32):
    """Oracle of the fused pack-payload kernel on one flattened leaf
    c [R, C]: error-feedback injection, exact-k selection, QSGD
    quantization, then ENCODING as an actual wire buffer instead of a
    dense masked tree.  Returns (data, idx, scale, resid):

      data   encoding == "quant":  uint32 words [R, W] of bit-packed
                                   levels q + s (see pack_words)
             encoding == "quant_dense": all C levels bit-packed, no
                                   indices (masked levels encode 0)
             encoding == "sparse": kept values [R, k] in c.dtype
             encoding == "dense":  the full masked/quantized chat [R, C]
      idx    kept column indices [R, k] (ascending; iota when k == C)
      scale  per-row quantization scale [R, 1] in compute_dtype(c.dtype)
             (zeros when bits >= 32)
      resid  ceff - chat in c.dtype (the error-feedback update), where
             chat is exactly what decode_payload_ref reconstructs

    The selection/quantization math is compress_correction_ref's, on the
    same uniform draws — so the packed payload round-trips to the dense
    compressed correction bitwise (mod -0.0 lost to the scatter-add)."""
    ct = compute_dtype(c.dtype)
    ceff = c.astype(ct) if e is None else c.astype(ct) + e.astype(ct)
    n = ceff.shape[-1]
    if k < n:
        score = jnp.abs(ceff) if mode == "topk" else u_sel.astype(ct)
        mask = exact_k_mask(score, k)
        kept = jnp.where(mask, ceff, jnp.zeros_like(ceff))
        idx = kept_indices(mask, k)
    else:
        kept = ceff
        idx = jax.lax.broadcasted_iota(
            jnp.int32, (*ceff.shape[:-1], k), ceff.ndim - 1
        )
    if bits < 32:
        q, scale = quantize_levels(kept, u_rnd, bits, ct)
        chat = dequantize_levels(q, scale, bits, ct)
    else:
        q, scale = kept, jnp.zeros((*ceff.shape[:-1], 1), ct)
        chat = kept
    chat_out = chat.astype(c.dtype)
    resid = (ceff - chat_out.astype(ct)).astype(c.dtype)
    if encoding in ("quant", "quant_dense"):
        s = 2 ** (bits - 1) - 1
        qk = q if encoding == "quant_dense" else jnp.take_along_axis(
            q, idx, axis=-1
        )
        levels = (qk + float(s)).astype(jnp.int32).astype(jnp.uint32)
        data = pack_words(levels, bits)
    elif encoding == "sparse":
        data = jnp.take_along_axis(chat_out, idx, axis=-1)
    elif encoding == "dense":
        data = chat_out
    else:
        raise ValueError(f"unknown payload encoding {encoding!r}")
    return data, idx.astype(index_dtype), scale, resid


def decode_payload_ref(data, idx, scale, *, cols: int, dtype, k: int,
                       bits: int, encoding: str = "quant"):
    """Inverse of pack_payload_ref: scatter-add the packed payload back
    into the dense [R, cols] compressed correction the agents apply.
    Bitwise equal to the chat that produced the payload (the dequant is
    the same dequantize_levels expression on the same operands; kept
    slots land via exact scatter-add into zeros)."""
    if encoding == "dense":
        return data
    ct = compute_dtype(dtype)
    s = 2 ** (bits - 1) - 1
    if encoding == "quant_dense":
        # implicit indices: every level of the row is present (masked
        # levels decode to exact zeros) — no scatter needed
        levels = unpack_words(data, cols, bits).astype(jnp.int32)
        q = levels.astype(ct) - float(s)
        return dequantize_levels(q, scale.astype(ct), bits, ct).astype(dtype)
    ii = idx.astype(jnp.int32)
    if encoding == "sparse":
        vals = data
    else:
        levels = unpack_words(data, k, bits).astype(jnp.int32)
        q = levels.astype(ct) - float(s)
        vals = dequantize_levels(q, scale.astype(ct), bits, ct).astype(dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, ii.shape, 0)
    dense = jnp.zeros((*ii.shape[:-1], cols), dtype)
    return dense.at[rows, ii].add(vals)


def flash_attention_ref(
    q, k, v, *, causal: bool = True, window: int = 0, softcap: float = 0.0
):
    """q [B,H,Sq,hd], k/v [B,H,Skv,hd] (heads already grouped/repeated)."""
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    Sq, Skv = q.shape[2], k.shape[2]
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= qp - kp < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def ssm_scan_ref(dt, x, A, Bc, Cc, state0=None):
    """Sequential oracle of the selective scan
    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t;  y_t = <h_t, C_t>.

    dt, x [Bt, S, D], A [D, N], Bc, Cc [Bt, S, N]; state0 [Bt, D, N]
    (zeros when None).  Returns (y [Bt, S, D], final state [Bt, D, N]).
    """
    if state0 is None:
        state0 = jnp.zeros((dt.shape[0],) + A.shape, dt.dtype)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    seq = tuple(jnp.swapaxes(u, 0, 1) for u in (dt, x, Bc, Cc))
    state, y = jax.lax.scan(step, state0, seq)
    return jnp.swapaxes(y, 0, 1), state
