"""falcon-mamba-7b: a stack of Mamba-1 layers as the repository defines
them (causal depthwise conv, selective scan run step by step over time,
gated RMSNorm on the output; the departures from FalconMamba are listed
under `assumed` in the configuration file) and an LM head tied to the
embedding, scaled by sqrt(d) on the way in."""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from chipbench.archs import common
from chipbench.archs.common import HIGHEST

#: the program's registry name of this architecture (`launch/train.py --arch`)
PROGRAM_ARCH = "falcon-mamba-7b"


def program_args(c: Dict) -> List[str]:
    return common.program_args(c, PROGRAM_ARCH)


def program_fields(c: Dict) -> Dict:
    if c["time_step_rank"] != max(1, c["hidden_size"] // 16):
        raise ValueError("time_step_rank differs from the program's d_model // 16")
    return {**common.program_fields(c), "d_inner": c["intermediate_size"],
            "ssm_state": c["state_size"], "conv_width": c["conv_kernel"]}


def _mamba_layer(key, c, dtype):
    d, di, N = c["hidden_size"], c["intermediate_size"], c["state_size"]
    W, r = c["conv_kernel"], c["time_step_rank"]
    ks = jax.random.split(jax.random.split(key, 4)[0], 8)
    s_in, s_inner = 1.0 / jnp.sqrt(d), 1.0 / jnp.sqrt(di)
    a = jnp.arange(1, N + 1, dtype=jnp.float32)
    return {
        "ln1": {"scale": jnp.zeros((d,), dtype)},
        "mamba": {
            "in_proj": common.normal(ks[0], (d, 2 * di), s_in, dtype),
            "conv_w": common.normal(ks[1], (W, di), 0.5, dtype),
            "conv_b": jnp.zeros((di,), dtype),
            "out_proj": common.normal(ks[2], (di, d), s_inner, dtype),
            "D": jnp.ones((di,), dtype),
            "norm": jnp.zeros((di,), dtype),
            "x_proj": common.normal(ks[3], (di, r + 2 * N), s_inner, dtype),
            "dt_proj": (jax.random.normal(ks[4], (r, di)) / jnp.sqrt(r)).astype(dtype),
            "dt_bias": jnp.zeros((di,), dtype),
            "A_log": jnp.log(jnp.broadcast_to(a, (di, N))).astype(jnp.float32),
        },
    }


def _mamba_block(p, h, c):
    q = p["mamba"]
    N, r = c["state_size"], c["time_step_rank"]
    S = h.shape[1]
    u = common.rms(h, p["ln1"]["scale"])
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
    y = common.rms(y * jax.nn.silu(z), q["norm"])
    return h + jnp.matmul(y, q["out_proj"], precision=HIGHEST)


LAYERS = {"mamba1": _mamba_layer}
BLOCKS = {"mamba1": _mamba_block}


def init_params(key, c: Dict, dtype=jnp.float32):
    return common.init_params(key, c, LAYERS, dtype)


def agent_loss(params, delta, batch, c: Dict):
    return common.tied_lm_loss(params, delta, batch, c, BLOCKS)


def forward_flops(c: Dict, batch: int, seq: int, *,
                  causal_half: bool = True) -> Dict[str, float]:
    """The projections and the readout y_t = C_t . h_t lower to dots;
    the conv and the scan's dA * h + dt x B (three products and one add
    per state entry) run on the vector unit."""
    T = batch * seq
    d, L = c["hidden_size"], c["num_hidden_layers"]
    di, N = c["intermediate_size"], c["state_size"]
    r, W = c["time_step_rank"], c["conv_kernel"]
    proj = 2 * T * (d * 2 * di + di * (r + 2 * N) + r * di + di * d)
    readout = 2 * T * di * N
    mm = L * (proj + readout) + common.lm_head_flops(c, batch, seq)
    ew = L * (2 * T * di * W + 4 * T * di * N)
    return {"matmul": float(mm), "elementwise": float(ew)}


def kernel_bytes(c: Dict, traffic: Dict) -> Dict[str, float]:
    """HBM bytes per round that the selective-scan kernels
    (`kernels/ssm_scan.py selective_scan`: `ssm_scan_fwd`, and
    `ssm_scan_bwd` for its gradient) cannot do without, in float32:
    every operand read once and every result written once.  A gradient
    evaluation makes one call of each per layer over the rows of all m
    agents; a round makes K evaluations (`flops.round_flops`).

    - forward: dt, x [rows, S, d_inner], A [d_inner, N] (once a call:
      the agents may share it), B, C [rows, S, N] in; y [rows, S,
      d_inner] out;
    - backward: the forward's inputs and dy in; ddt, dx, dB, dC and dA,
      one [d_inner, N] per agent, out.

    The state entering each time chunk, which the forward saves for the
    backward, and the backward's partial sums of dB and dC per block of
    channels depend on the kernels' tiling, and are left out: the count
    holds for any tiling, so the share of the bandwidth peak it gives
    cannot pass 1."""
    m, K = traffic["agents"], traffic["local_steps"]
    T = m * traffic["per_agent_batch"] * traffic["seq_len"]
    di, N = c["intermediate_size"], c["state_size"]
    seq, cols, a = T * di, T * N, di * N  # dt-like, B-like, A-like
    fwd = 3 * seq + a + 2 * cols
    bwd = (3 * seq + a + 2 * cols) + (2 * seq + 2 * cols + m * a)
    calls = c["num_hidden_layers"] * K
    return {"ssm_scan": 4.0 * calls * (fwd + bwd)}
