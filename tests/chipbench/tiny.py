"""Small cells for the benchmark's CPU tests: the registry's reduced
widths, one layer, a few agents with short sequences."""
import copy

from chipbench import registry

DENSE = {
    "name": "tiny-dense", "arch": "granite-8b", "preset": "reduced",
    "layer_pattern": ["attn"], "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 512,
    "num_hidden_layers": 1, "vocab_size": 128, "rope_theta": 10000.0,
}
MAMBA = {
    "name": "tiny-mamba", "arch": "falcon-mamba-7b", "preset": "reduced",
    "layer_pattern": ["mamba1"], "hidden_size": 256, "intermediate_size": 512,
    "state_size": 16, "conv_kernel": 4, "time_step_rank": 16,
    "num_hidden_layers": 1, "vocab_size": 128,
}
TRAFFIC = {
    "algorithm": "fedgda_gt", "strategy": {}, "agents": 4,
    "per_agent_batch": 1, "seq_len": 32, "local_steps": 2, "eta": 0.002,
    "heterogeneity": 7, "check_rounds": 3, "trace_rounds": 2,
}
#: a tiny stand-in for each kind of cell, with the limits of the real
#: cell it stands for
KINDS = {
    "dense": (DENSE, "granite-8b.gt.m8.b4s64"),
    "mamba": (MAMBA, "falcon-mamba-7b.gt.m2.s512"),
}


def cell(kind: str) -> dict:
    cfg, workload = KINDS[kind]
    real = registry.cell(registry.load_benchmark(), workload)
    return {**real, "config": copy.deepcopy(cfg),
            "traffic": copy.deepcopy(TRAFFIC), "limits": dict(real["limits"])}


def devices():
    import jax

    return jax.devices()[:1]
