"""What the architecture modules share: the plain float32 building
blocks at `Precision.HIGHEST`, the dense decoder layer, the walk over a
layer pattern, the tied LM loss, and the program's common cuts.

Nothing here imports the program under test; weights are drawn as the
program draws them, so the same seed gives the same model on both sides.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
RMS_EPS = 1e-6


# ------------------------------------------------------------ the program
def program_args(c: Dict, arch: str) -> List[str]:
    """`launch/train.py` arguments that cut the program's `arch` to the
    file's depth and vocabulary."""
    args = ["--arch", arch, "--num-layers", str(c["num_hidden_layers"]),
            "--vocab-size", str(c["vocab_size"])]
    if c.get("preset") == "reduced":
        args.append("--reduced")
    return args


def program_fields(c: Dict) -> Dict:
    """The `ModelConfig` fields every architecture has, as the file
    states them."""
    return {"d_model": c["hidden_size"], "num_layers": c["num_hidden_layers"],
            "vocab_size": c["vocab_size"], "pattern": tuple(c["layer_pattern"])}


# ----------------------------------------------------------------- weights
def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense_layer(key, c, dtype):
    d, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = c["head_dim"], c["intermediate_size"]
    ks = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(ks[0], 4)
    kg, ku, kd = jax.random.split(ks[1], 3)
    s = 1.0 / jnp.sqrt(d)
    return {
        "ln1": {"scale": jnp.zeros((d,), dtype)},
        "attn": {
            "wq": normal(kq, (d, H, hd), s, dtype),
            "wk": normal(kk, (d, KV, hd), s, dtype),
            "wv": normal(kv, (d, KV, hd), s, dtype),
            "wo": normal(ko, (H, hd, d), 1.0 / jnp.sqrt(H * hd), dtype),
        },
        "ln2": {"scale": jnp.zeros((d,), dtype)},
        "mlp": {
            "gate": normal(kg, (d, ff), s, dtype),
            "up": normal(ku, (d, ff), s, dtype),
            "down": normal(kd, (ff, d), 1.0 / jnp.sqrt(ff), dtype),
        },
    }


def init_params(key, c: Dict, layers: Dict[str, Callable], dtype):
    """Weights of a stack that cycles `c["layer_pattern"]`, as the
    program's `models/transformer.py init_params` draws them: the j-th
    kind of the pattern under `blocks["{j}_{kind}"]`, stacked over the
    pattern's periods, drawn by `layers[kind](key, c, dtype)`; then the
    final norm and the embedding."""
    pattern = c["layer_pattern"]
    keys = jax.random.split(key, len(pattern) + 4)
    periods = c["num_hidden_layers"] // len(pattern)
    blocks = {}
    for j, kind in enumerate(pattern):
        lk = jax.random.split(keys[j], periods)
        blocks[f"{j}_{kind}"] = jax.vmap(lambda k, kind=kind: layers[kind](k, c, dtype))(lk)
    return {
        "blocks": blocks,
        "final_norm": {"scale": jnp.zeros((c["hidden_size"],), dtype)},
        "embed": normal(keys[len(pattern)], (c["vocab_size"], c["hidden_size"]), 0.02, dtype),
    }


# ------------------------------------------------------------------- model
def rms(x, scale):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * (1.0 + scale.astype(jnp.float32))


def rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def dense_block(p, h, c):
    """RMSNorm, grouped-query attention with half-split RoPE and a causal
    mask, SwiGLU MLP, each on the residual stream."""
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    S = h.shape[1]
    a = rms(h, p["ln1"]["scale"])
    q = rope(jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wq"], precision=HIGHEST), c["rope_theta"])
    k = rope(jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wk"], precision=HIGHEST), c["rope_theta"])
    v = jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wv"], precision=HIGHEST)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bsnh,btnh->bnst", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -1e30)
    o = jnp.einsum("bnst,btnh->bsnh", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    h = h + jnp.einsum("bsnh,nhd->bsd", o, p["attn"]["wo"], precision=HIGHEST)
    m = rms(h, p["ln2"]["scale"])
    gate = jax.nn.silu(jnp.matmul(m, p["mlp"]["gate"], precision=HIGHEST))
    up = jnp.matmul(m, p["mlp"]["up"], precision=HIGHEST)
    return h + jnp.matmul(gate * up, p["mlp"]["down"], precision=HIGHEST)


def walk(blocks, h, c: Dict, forward: Dict[str, Callable]):
    """The residual stream through the stack, period by period and in
    the pattern's order within a period, layer j of kind `kind` by
    `forward[kind](its weights, h, c)`."""
    pattern = c["layer_pattern"]
    for i in range(c["num_hidden_layers"] // len(pattern)):
        for j, kind in enumerate(pattern):
            h = forward[kind](jax.tree.map(lambda u: u[i], blocks[f"{j}_{kind}"]), h, c)
    return h


def tied_lm_loss(params, delta, batch, c: Dict, forward: Dict[str, Callable]):
    """Mean next-token cross-entropy of one agent's batch with `delta`
    added to every input embedding (scaled by sqrt(d)), through the
    stack, a final RMSNorm and the LM head tied to the embedding."""
    emb = params["embed"]
    h = emb[batch["tokens"]] * math.sqrt(c["hidden_size"]) + delta["delta"]
    h = walk(params["blocks"], h, c, forward)
    h = rms(h, params["final_norm"]["scale"])
    logits = jnp.einsum("bsd,vd->bsv", h, emb, precision=HIGHEST)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------- FLOPs
def dense_layer_flops(c: Dict, batch: int, seq: int, causal_half: bool) -> float:
    """Matmul FLOPs of one dense decoder layer's forward pass."""
    T = batch * seq
    d, H, KV, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    proj = 2 * T * d * (2 * H * hd + 2 * KV * hd)  # q, o; k, v
    scores = 2 * 2 * batch * H * seq * seq * hd  # q k^T and p v
    if causal_half:
        scores /= 2
    mlp = 3 * 2 * T * d * c["intermediate_size"]
    return proj + scores + mlp


def lm_head_flops(c: Dict, batch: int, seq: int) -> int:
    return 2 * batch * seq * c["hidden_size"] * c["vocab_size"]
