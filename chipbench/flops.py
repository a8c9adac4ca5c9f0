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

The count of each architecture's forward pass lives in its module,
`chipbench/archs/<arch>.py`, beside its reference model; this file
holds what every architecture shares.

FedGDA-GT's fused round evaluates m x K gradients: the exchange at the
anchor point, whose gradient also serves the first local step, and K - 1
further local steps per agent.
"""
from __future__ import annotations

from typing import Dict

from . import registry


def forward_flops(c: Dict, batch: int, seq: int, *,
                  causal_half: bool = True) -> Dict[str, float]:
    """Forward FLOPs of one agent's batch, split into `matmul` (products
    that lower to dots) and `elementwise` (such as the SSM's conv and
    scan), counted by the module of the configuration's `arch`."""
    return registry.arch(c["arch"]).forward_flops(c, batch, seq, causal_half=causal_half)


def gradient_flops(c: Dict, batch: int, seq: int, **kw) -> float:
    """Matmul FLOPs of one gradient evaluation."""
    return 3.0 * forward_flops(c, batch, seq, **kw)["matmul"]


def round_flops(c: Dict, traffic: Dict) -> float:
    """Model FLOPs of one round: m agents x K gradient evaluations."""
    return (traffic["agents"] * traffic["local_steps"]
            * gradient_flops(c, traffic["per_agent_batch"], traffic["seq_len"]))
