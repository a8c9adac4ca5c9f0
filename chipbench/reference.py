"""Plain float32 reference of the benchmark's federated round.

Independent of the program under test: it imports nothing from `repro`
and builds its weights, data, model and FedGDA-GT round from the
configuration file's numbers and the seed alone.  Every matmul runs at
`Precision.HIGHEST`, so on a TPU the reference is float32 arithmetic,
not the bf16 passes of the chip's default precision.

What it mirrors from the published description:

- the model, by the module that the configuration's `arch` names,
  `chipbench/archs/<arch>.py` (found by `registry.arch`): its weights
  and its whole loss, the adversarial-embedding objective (one
  perturbation `delta` added to every input embedding, mean token
  cross-entropy) with the embedding scale, the layer stack and the head
  of that architecture;
- FedGDA-GT (Algorithm 2), literally: every agent takes K local steps
  `z <- z -/+ eta (g_i(z) + c_i)` with `c_i = gbar - g_i(z0)`, the server
  averages, and `delta` is projected onto the unit L2 ball.

This file holds what every architecture shares: the seed's keys, the
data, the round and its projection.  The seed-to-weights and
seed-to-tokens maps follow the deployment's documented draws (normal
weights at the published init scales, Zipf token streams shifted by
`agent * heterogeneity`), so the same seed gives the same problem on
both sides.  Agents are processed one at a time, so
the reference holds a handful of model copies whatever the agent count.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp

from . import registry

ZIPF_A = 1.2


def seed_keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    """(weights key, data key) for a seed of up to 64 bits: the low and
    high 32-bit halves both enter the key, so no two seeds alias."""
    base = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


# ------------------------------------------------------------------- model
def init_params(key, c: Dict, dtype=jnp.float32):
    """Weights of the configuration `c` (layer stacks keep a leading
    depth axis), drawn from `key` by its architecture's module."""
    return registry.arch(c["arch"]).init_params(key, c, dtype)


def agent_loss(params, delta, batch, c: Dict):
    """Mean next-token cross-entropy of one agent's batch with `delta`
    added to every input embedding, by the architecture's module."""
    return registry.arch(c["arch"]).agent_loss(params, delta, batch, c)


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
