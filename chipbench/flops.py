"""Model FLOPs of one federated round, counted from shapes.

A multiply-add is two FLOPs.  One gradient evaluation is the forward
pass plus a backward pass of twice its work (the gradient with respect
to each operand of every product).  Work that the program recomputes
(checkpointed LM-head chunks, attention blocks) is not model work and is
not counted.  Causal attention needs half of the score matrix, and is
counted at half.  A round's FLOPs are its products that lower to dots
(the MXU's work, held against the bf16 matmul peak); the SSM's conv and
scan arithmetic runs on the vector unit and is reported apart by
`forward_flops`, not counted in a round.

FedGDA-GT's fused round evaluates m x K gradients: the exchange at the
anchor point, whose gradient also serves the first local step, and K - 1
further local steps per agent.
"""
from __future__ import annotations

from typing import Dict


def forward_flops(c: Dict, batch: int, seq: int, *,
                  causal_half: bool = True) -> Dict[str, float]:
    """Forward FLOPs of one agent's batch, split into `matmul` (products
    that lower to dots) and `elementwise` (the SSM's conv and scan)."""
    T = batch * seq
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    kind = c["layer_pattern"][0]
    mm = ew = 0.0
    if kind == "attn":
        H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
        ff = c["intermediate_size"]
        proj = 2 * T * d * (2 * H * hd + 2 * KV * hd)  # q, o; k, v
        scores = 2 * 2 * batch * H * seq * seq * hd  # q k^T and p v
        if causal_half:
            scores /= 2
        mlp = 3 * 2 * T * d * ff
        mm += L * (proj + scores + mlp)
    elif kind == "mamba1":
        di, N = c["intermediate_size"], c["state_size"]
        r, W = c["time_step_rank"], c["conv_kernel"]
        proj = 2 * T * (d * 2 * di + di * (r + 2 * N) + r * di + di * d)
        readout = 2 * T * di * N  # y_t = C_t . h_t
        mm += L * (proj + readout)
        # conv, dA * h + dt x B (three products, one add per state entry)
        ew += L * (2 * T * di * W + 4 * T * di * N)
    else:
        raise ValueError(f"no FLOP count for layer kind {kind!r}")
    mm += 2 * T * d * V  # tied LM head
    return {"matmul": float(mm), "elementwise": float(ew)}


def gradient_flops(c: Dict, batch: int, seq: int, **kw) -> float:
    """Matmul FLOPs of one gradient evaluation."""
    return 3.0 * forward_flops(c, batch, seq, **kw)["matmul"]


def round_flops(c: Dict, traffic: Dict) -> float:
    """Model FLOPs of one round: m agents x K gradient evaluations."""
    return (traffic["agents"] * traffic["local_steps"]
            * gradient_flops(c, traffic["per_agent_batch"], traffic["seq_len"]))
