"""Plain float32 reference of the benchmark's federated round.

Independent of the program under test: it imports nothing from `repro`
and builds its weights, data, model and FedGDA-GT round from the
configuration file's numbers and the seed alone.  Every matmul runs at
`Precision.HIGHEST`, so on a TPU the reference is float32 arithmetic,
not the bf16 passes of the chip's default precision.

What it mirrors from the published description:

- the dense decoder layer (granite): RMSNorm, grouped-query attention
  with half-split RoPE and a causal mask, SwiGLU MLP, tied LM head;
- the Mamba-1 layer (falcon-mamba) as the repository defines it: causal
  depthwise conv, selective scan run step by step over time, gated
  RMSNorm on the output (the departures from FalconMamba are listed under
  `assumed` in the configuration file);
- the adversarial-embedding objective: one perturbation `delta` added to
  every input embedding, mean token cross-entropy;
- FedGDA-GT (Algorithm 2), literally: every agent takes K local steps
  `z <- z -/+ eta (g_i(z) + c_i)` with `c_i = gbar - g_i(z0)`, the server
  averages, and `delta` is projected onto the unit L2 ball.

The seed-to-weights and seed-to-tokens maps follow the deployment's
documented draws (normal weights at the published init scales, Zipf
token streams shifted by `agent * heterogeneity`), so the same seed gives
the same problem on both sides.  Agents are processed one at a time, so
the reference holds a handful of model copies whatever the agent count.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
RMS_EPS = 1e-6
ZIPF_A = 1.2


def seed_keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    """(weights key, data key) for a seed of up to 64 bits: the low and
    high 32-bit halves both enter the key, so no two seeds alias."""
    base = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


# ----------------------------------------------------------------- weights
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _dense_layer(key, c, dtype):
    d, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = c["head_dim"], c["intermediate_size"]
    ks = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(ks[0], 4)
    kg, ku, kd = jax.random.split(ks[1], 3)
    s = 1.0 / jnp.sqrt(d)
    return {
        "ln1": {"scale": jnp.zeros((d,), dtype)},
        "attn": {
            "wq": _normal(kq, (d, H, hd), s, dtype),
            "wk": _normal(kk, (d, KV, hd), s, dtype),
            "wv": _normal(kv, (d, KV, hd), s, dtype),
            "wo": _normal(ko, (H, hd, d), 1.0 / jnp.sqrt(H * hd), dtype),
        },
        "ln2": {"scale": jnp.zeros((d,), dtype)},
        "mlp": {
            "gate": _normal(kg, (d, ff), s, dtype),
            "up": _normal(ku, (d, ff), s, dtype),
            "down": _normal(kd, (ff, d), 1.0 / jnp.sqrt(ff), dtype),
        },
    }


def _mamba_layer(key, c, dtype):
    d, di, N = c["hidden_size"], c["intermediate_size"], c["state_size"]
    W, r = c["conv_kernel"], c["time_step_rank"]
    ks = jax.random.split(jax.random.split(key, 4)[0], 8)
    s_in, s_inner = 1.0 / jnp.sqrt(d), 1.0 / jnp.sqrt(di)
    a = jnp.arange(1, N + 1, dtype=jnp.float32)
    return {
        "ln1": {"scale": jnp.zeros((d,), dtype)},
        "mamba": {
            "in_proj": _normal(ks[0], (d, 2 * di), s_in, dtype),
            "conv_w": _normal(ks[1], (W, di), 0.5, dtype),
            "conv_b": jnp.zeros((di,), dtype),
            "out_proj": _normal(ks[2], (di, d), s_inner, dtype),
            "D": jnp.ones((di,), dtype),
            "norm": jnp.zeros((di,), dtype),
            "x_proj": _normal(ks[3], (di, r + 2 * N), s_inner, dtype),
            "dt_proj": (jax.random.normal(ks[4], (r, di)) / jnp.sqrt(r)).astype(dtype),
            "dt_bias": jnp.zeros((di,), dtype),
            "A_log": jnp.log(jnp.broadcast_to(a, (di, N))).astype(jnp.float32),
        },
    }


_LAYERS = {"attn": _dense_layer, "mamba1": _mamba_layer}


def init_params(key, c: Dict, dtype=jnp.float32):
    """Weights of the configuration `c` (layer stacks keep a leading
    depth axis), drawn from `key`."""
    kind = c["layer_pattern"][0]
    keys = jax.random.split(key, len(c["layer_pattern"]) + 4)
    lk = jax.random.split(keys[0], c["num_hidden_layers"])
    layers = jax.vmap(lambda k: _LAYERS[kind](k, c, dtype))(lk)
    return {
        "blocks": {f"0_{kind}": layers},
        "final_norm": {"scale": jnp.zeros((c["hidden_size"],), dtype)},
        "embed": _normal(keys[1], (c["vocab_size"], c["hidden_size"]), 0.02, dtype),
    }


# -------------------------------------------------------------------- data
def make_data(key, agents: int, batch: int, seq_len: int, vocab: int,
              heterogeneity: int) -> Dict:
    """Agent-stacked next-token batches: Zipf(1.2) token streams, agent
    i's ids shifted by i * heterogeneity (mod vocab)."""
    logits = -ZIPF_A * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    keys = jax.random.split(key, agents)
    toks = jnp.stack([
        (jax.random.categorical(keys[i], logits, shape=(batch, seq_len + 1))
         + i * heterogeneity) % vocab
        for i in range(agents)
    ])
    return {"tokens": toks[..., :-1].astype(jnp.int32),
            "labels": toks[..., 1:].astype(jnp.int32)}


# ------------------------------------------------------------------- model
def _rms(x, scale):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dense_block(p, h, c):
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    S = h.shape[1]
    a = _rms(h, p["ln1"]["scale"])
    q = _rope(jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wq"], precision=HIGHEST), c["rope_theta"])
    k = _rope(jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wk"], precision=HIGHEST), c["rope_theta"])
    v = jnp.einsum("bsd,dnh->bsnh", a, p["attn"]["wv"], precision=HIGHEST)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bsnh,btnh->bnst", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -1e30)
    o = jnp.einsum("bnst,btnh->bsnh", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    h = h + jnp.einsum("bsnh,nhd->bsd", o, p["attn"]["wo"], precision=HIGHEST)
    m = _rms(h, p["ln2"]["scale"])
    gate = jax.nn.silu(jnp.matmul(m, p["mlp"]["gate"], precision=HIGHEST))
    up = jnp.matmul(m, p["mlp"]["up"], precision=HIGHEST)
    return h + jnp.matmul(gate * up, p["mlp"]["down"], precision=HIGHEST)


def _mamba_block(p, h, c):
    q = p["mamba"]
    N, r = c["state_size"], c["time_step_rank"]
    S = h.shape[1]
    u = _rms(h, p["ln1"]["scale"])
    x, z = jnp.split(jnp.matmul(u, q["in_proj"], precision=HIGHEST), 2, axis=-1)
    W = q["conv_w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    x = sum(xp[:, w:w + S] * q["conv_w"][w] for w in range(W)) + q["conv_b"]
    x = jax.nn.silu(x)
    dt_raw, Bc, Cc = jnp.split(
        jnp.matmul(x, q["x_proj"], precision=HIGHEST), [r, r + N], axis=-1)
    dt = jax.nn.softplus(jnp.matmul(dt_raw, q["dt_proj"], precision=HIGHEST) + q["dt_bias"])
    A = -jnp.exp(q["A_log"])  # [di, N]

    def step(state, inp):  # state [B, di, N]
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[..., None] * A) * state + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.einsum("bdn,bn->bd", state, c_t, precision=HIGHEST)

    B, di = x.shape[0], x.shape[-1]
    seq = tuple(jnp.swapaxes(t, 0, 1) for t in (dt, x, Bc, Cc))
    _, y = jax.lax.scan(step, jnp.zeros((B, di, N), jnp.float32), seq)
    y = jnp.swapaxes(y, 0, 1) + q["D"] * x
    y = _rms(y * jax.nn.silu(z), q["norm"])
    return h + jnp.matmul(y, q["out_proj"], precision=HIGHEST)


_BLOCKS = {"attn": _dense_block, "mamba1": _mamba_block}


def agent_loss(params, delta, batch, c: Dict):
    """Mean next-token cross-entropy of one agent's batch with `delta`
    added to every input embedding."""
    emb = params["embed"]
    h = emb[batch["tokens"]] * math.sqrt(c["hidden_size"]) + delta["delta"]
    kind = c["layer_pattern"][0]
    layers = params["blocks"][f"0_{kind}"]
    for i in range(c["num_hidden_layers"]):
        h = _BLOCKS[kind](jax.tree.map(lambda u: u[i], layers), h, c)
    h = _rms(h, params["final_norm"]["scale"])
    logits = jnp.einsum("bsd,vd->bsv", h, emb, precision=HIGHEST)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------ round
def _project(y, radius=1.0):
    norm = jnp.sqrt(jnp.maximum(jnp.sum(y["delta"] ** 2), 1e-30))
    return {"delta": y["delta"] * jnp.minimum(1.0, radius / norm)}


class FedGDAGT:
    """FedGDA-GT rounds for one configuration and traffic mix, one agent
    at a time.  `rounds(x, y, data, n)` yields (x_t, y_t) after each of n
    rounds."""

    def __init__(self, c: Dict, local_steps: int, eta: float):
        self.K, self.eta = local_steps, eta
        self.grad = jax.jit(jax.grad(lambda x, y, b: agent_loss(x, y, b, c), argnums=(0, 1)))
        self.loss = jax.jit(lambda x, y, b: agent_loss(x, y, b, c))
        tm = jax.tree.map
        self._add = jax.jit(lambda a, b: tm(jnp.add, a, b))
        self._corr = jax.jit(lambda gbar, g: tm(jnp.subtract, gbar, g))
        self._scale = jax.jit(lambda a, s: tm(lambda u: u * s, a))
        eta = self.eta

        def local(zx, zy, g, cc):
            (gx, gy), (cx, cy) = g, cc
            return (tm(lambda u, a, b: u - eta * (a + b), zx, gx, cx),
                    tm(lambda u, a, b: u + eta * (a + b), zy, gy, cy))

        self._local = jax.jit(local)
        self._project = jax.jit(_project)

    def rounds(self, x, y, data, n: int) -> Iterator[Tuple[Dict, Dict]]:
        m = data["tokens"].shape[0]
        agent = [jax.tree.map(lambda u: u[i], data) for i in range(m)]
        for _ in range(n):
            gsum = None
            for b in agent:
                g = self.grad(x, y, b)
                gsum = g if gsum is None else self._add(gsum, g)
            gbar = self._scale(gsum, 1.0 / m)
            del gsum
            acc = None
            for b in agent:
                g0 = self.grad(x, y, b)
                cc = self._corr(gbar, g0)
                zx, zy = self._local(x, y, g0, cc)
                del g0
                for _k in range(1, self.K):
                    zx, zy = self._local(zx, zy, self.grad(zx, zy, b), cc)
                acc = (zx, zy) if acc is None else self._add(acc, (zx, zy))
                del cc, zx, zy
            x, y = self._scale(acc, 1.0 / m)
            y = self._project(y)
            del acc, gbar
            yield x, y

    def global_loss(self, x, y, data) -> float:
        m = data["tokens"].shape[0]
        return sum(float(self.loss(x, y, jax.tree.map(lambda u: u[i], data)))
                   for i in range(m)) / m


def leaf_paths(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
