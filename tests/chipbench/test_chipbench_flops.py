"""The benchmark's model-FLOP function and peak table."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, harness, peaks

import tiny


def test_dense_forward_flops_equal_a_hand_count():
    # d 256, 4 query and 4 KV heads of 32, d_ff 512, vocab 128; 1 x 32 tokens
    T = 32
    proj = 2 * T * 256 * (4 * 32) * 4          # q, k, v, o
    scores = 2 * 2 * 4 * 32 * 32 * 32 // 2     # q k^T and p v, causal half
    mlp = 3 * 2 * T * 256 * 512                # gate, up, down
    head = 2 * T * 256 * 128                   # tied LM head
    f = flops.forward_flops(tiny.DENSE, 1, 32)
    assert f == {"matmul": proj + scores + mlp + head, "elementwise": 0.0}
    assert flops.gradient_flops(tiny.DENSE, 1, 32) == 3 * f["matmul"]


def test_mamba_forward_flops_equal_a_hand_count():
    # d 256, d_inner 512, state 16, dt_rank 16, conv 4; 1 x 32 tokens
    T = 32
    proj = 2 * T * (256 * 1024 + 512 * 48 + 16 * 512 + 512 * 256)
    readout = 2 * T * 512 * 16
    head = 2 * T * 256 * 128
    conv, scan = 2 * T * 512 * 4, 4 * T * 512 * 16
    f = flops.forward_flops(tiny.MAMBA, 1, 32)
    assert f == {"matmul": proj + readout + head, "elementwise": conv + scan}
    # the vector unit's conv and scan are not held against the MXU peak
    assert flops.gradient_flops(tiny.MAMBA, 1, 32) == 3 * f["matmul"]


def test_round_flops_are_m_times_k_gradients():
    t = dict(tiny.TRAFFIC, agents=3, local_steps=5)
    assert flops.round_flops(tiny.DENSE, t) == 15 * flops.gradient_flops(
        tiny.DENSE, 1, 32)


@pytest.mark.parametrize("traffic,tflop", [("gt.m4.s2048", 24.74), ("gt.m8.b4s64", 5.985)])
def test_granite_cell_round_is_about_25_tflop(traffic, tflop):
    """granite-8b.L1.v6144 under 4 agents x 2048 tokens (ISSUE 12's count,
    about 25 TFLOP) and under 8 agents x 4 x 64 tokens."""
    from chipbench import registry

    bench = registry.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "granite-8b.L1.v6144")
    assert flops.round_flops(registry.config(entry), registry.traffic(traffic)) \
        == pytest.approx(tflop * 1e12, rel=1e-3)


@pytest.mark.parametrize("kind", ["dense", "mamba"])
def test_matmul_flops_agree_with_the_hlo_census(kind):
    """Executed dots of the compiled tiny round, counted from its HLO,
    against m x K gradients of the FLOP function's dots (attention
    counted whole, as the program computes it).  The backward of the
    SSM readout y = C . h with respect to h is an outer product, which
    lowers to a multiply rather than a dot, so the census has one
    readout fewer per gradient."""
    from repro.launch.hlo_census import HloCensus

    cell = tiny.cell(kind)
    c, t = cell["config"], cell["traffic"]
    prog = harness.Program(c, t, 1, jnp.float32, jax.devices()[:1])
    census = HloCensus(prog.rnd.as_text()).summary()["executed_dot_flops"]
    B, S = t["per_agent_batch"], t["seq_len"]
    per_grad = 3 * flops.forward_flops(c, B, S, causal_half=False)["matmul"]
    if kind == "mamba":
        per_grad -= 2 * B * S * c["intermediate_size"] * c["state_size"]
    assert census == t["agents"] * t["local_steps"] * per_grad


def test_peak_table_knows_v5e_and_refuses_unknown_devices():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9 imaginary")
