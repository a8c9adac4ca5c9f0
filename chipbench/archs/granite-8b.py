"""granite-8b: a stack of dense decoder layers (RMSNorm, grouped-query
attention with half-split RoPE and a causal mask, SwiGLU MLP) and an LM
head tied to the embedding, scaled by sqrt(d) on the way in."""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp

from chipbench.archs import common

#: the program's registry name of this architecture (`launch/train.py --arch`)
PROGRAM_ARCH = "granite-8b"
LAYERS = {"attn": common.dense_layer}
BLOCKS = {"attn": common.dense_block}


def program_args(c: Dict) -> List[str]:
    return common.program_args(c, PROGRAM_ARCH)


def program_fields(c: Dict) -> Dict:
    return {**common.program_fields(c),
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "rope_theta": c["rope_theta"]}


def init_params(key, c: Dict, dtype=jnp.float32):
    return common.init_params(key, c, LAYERS, dtype)


def agent_loss(params, delta, batch, c: Dict):
    return common.tied_lm_loss(params, delta, batch, c, BLOCKS)


def forward_flops(c: Dict, batch: int, seq: int, *,
                  causal_half: bool = True) -> Dict[str, float]:
    mm = (c["num_hidden_layers"] * common.dense_layer_flops(c, batch, seq, causal_half)
          + common.lm_head_flops(c, batch, seq))
    return {"matmul": float(mm), "elementwise": 0.0}
