"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so the Pallas kernels and the
fused federated round compile here for a v5e that is only described
(`jax.experimental.topologies`).  That finds what interpret mode cannot:
primitives Pallas cannot lower for a TPU, tiles that do not fit VMEM, a
program that does not fit the chip's memory.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports every test file.  The persistent compilation cache is off around
these compiles (an entry written for a described chip cannot be read
back without one).  Tracing runs with 64-bit mode off, as on the chip.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import federated_token_batches
from repro.fed.strategies import resolve_strategy
from repro.kernels import backend, ref
from repro.kernels.compress_correction import compress_correction_2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gt_update import gt_update_2d
from repro.kernels.pack_payload import pack_payload_2d, unpack_payload_2d
from repro.kernels.ssm_scan import selective_scan
from repro.launch.steps import build_train_step
from repro.launch.train import build_fused_round
from repro.models import init_params
from repro.problems.adversarial import init_delta, make_adversarial_loss

F32 = jnp.float32
# a real correction leaf: a granite-8b MLP matrix [d_model, d_ff] stacked
# over 2 agents, as the compressed strategies flatten it
R, C = 2 * 4096, 14336
K_SEL = C // 10
HBM_BYTES = 16e9  # one TPU v5e (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes
    ]
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile()


_WORDS = ref.word_layout(K_SEL, 8)[2]
_SCAN_SHAPES = [((2, 512, 8192), F32)] * 2 + [((8192, 16), F32)] + [
    ((2, 512, 16), F32)] * 2

COMPILES = {
    "gt_update_2d": (
        lambda z, g, c: gt_update_2d(
            z, g, c, eta=1e-3, sign=-1.0, interpret=False
        ),
        [((R, C), F32)] * 3,
    ),
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        [((1, 32, 2048, 128), F32)] * 3,
    ),
    # falcon-mamba-7b's cell: 2 agents x 512 tokens, d_inner 8192,
    # state 16, the forward and (through the gradient) the backward
    "selective_scan-fwd": (
        lambda *a: selective_scan(*a, interpret=False),
        _SCAN_SHAPES,
    ),
    "selective_scan-grad": (
        jax.grad(
            lambda *a: jnp.sum(selective_scan(*a, interpret=False)),
            argnums=range(5),
        ),
        _SCAN_SHAPES,
    ),
    "compress_correction_2d-quantize_only": (
        lambda c, e, u: compress_correction_2d(
            c, e, None, u, k=C, bits=8, interpret=False
        ),
        [((R, C), F32)] * 3,
    ),
    "pack_payload_2d-dense": (
        lambda c: pack_payload_2d(
            c, None, None, None, k=C, bits=32, encoding="dense",
            interpret=False,
        ),
        [((R, C), F32)],
    ),
    "unpack_payload_2d-dense": (
        lambda d, i, s: unpack_payload_2d(
            d, i, s, cols=C, dtype=F32, k=C, bits=32, encoding="dense",
            interpret=False,
        ),
        [((R, C), F32), ((R, C), jnp.int32), ((R, 1), F32)],
    ),
}

# configurations a TPU refuses by name instead of lowering
# (kernels/backend.refuse_tpu_lowering), with the primitive named
REFUSED = {
    "compress_correction_2d-topk": (
        lambda c, e, u: compress_correction_2d(
            c, e, None, u, k=K_SEL, bits=8, interpret=False
        ),
        [((R, C), F32)] * 3, "top_k",
    ),
    "pack_payload_2d-select": (
        lambda c, e, u: pack_payload_2d(
            c, e, None, u, k=K_SEL, bits=8, interpret=False
        ),
        [((R, C), F32)] * 3, "top_k",
    ),
    "pack_payload_2d-bitpack": (
        lambda c, u: pack_payload_2d(
            c, None, None, u, k=C, bits=8, encoding="quant_dense",
            interpret=False,
        ),
        [((R, C), F32)] * 2, "bit-packing",
    ),
    "unpack_payload_2d-scatter": (
        lambda d, i, s: unpack_payload_2d(
            d, i, s, cols=C, dtype=F32, k=K_SEL, bits=8, interpret=False
        ),
        [((R, _WORDS), jnp.uint32), ((R, K_SEL), jnp.int32),
         ((R, 1), F32)],
        "scatter-add",
    ),
}


@pytest.mark.parametrize("name", sorted(COMPILES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = COMPILES[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_kernel_refused_on_tpu_names_primitive(name, one_chip):
    fn, shapes, primitive = REFUSED[name]
    with pytest.raises(NotImplementedError, match=primitive):
        _compile(fn, one_chip, *shapes)


def test_granite_round_fits_one_v5e(one_chip):
    """The round `chip_smoke.py` runs: granite-8b at published widths,
    1 layer, 2 agents, 1 x 2048 tokens each, K = 2, float32."""
    cfg = dataclasses.replace(get_config("granite-8b"), num_layers=1)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            tree,
        )

    with jax.enable_x64(False):
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg, F32)
        )
        data = jax.eval_shape(lambda: federated_token_batches(
            jax.random.PRNGKey(1), 2, 1, 2048, cfg.vocab_size,
            heterogeneity=7,
        ))
        rnd = build_fused_round(
            make_adversarial_loss(cfg, remat=False),
            resolve_strategy("fedgda_gt"), 2, 2e-3,
        )
        compiled = rnd.lower(
            on_chip(params), on_chip(init_delta(cfg)), on_chip(data)
        ).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    # the donation took: the new parameters reuse the old ones' buffers
    param_bytes = sum(u.size * u.dtype.itemsize
                      for u in jax.tree.leaves(params))
    assert ma.alias_size_in_bytes >= param_bytes


def test_falcon_mamba_round_takes_the_scan_kernel_on_v5e(one_chip,
                                                        monkeypatch):
    """The `falcon-mamba-7b.gt.m2.s512` cell's fused round (1 layer,
    vocabulary 8128, 2 agents x 1 x 512 tokens, K = 2), built as on a
    TPU: each of its two gradients runs `ssm_scan_fwd` and
    `ssm_scan_bwd`, no `while` loop carries a [..., 8192, 1, 16] scan
    state, and the program needs less than the 13.86 GB the jnp
    associative scan needed; the bound sits just above the 3.93 GB it
    needs with the kernels, so that [S, d_inner, N] residuals coming
    back (about 10 GB here) fail it."""
    # this host's backend is the CPU; the program is built for the chip
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("falcon-mamba-7b"), num_layers=1,
                              vocab_size=8128)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            tree,
        )

    with jax.enable_x64(False):
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg, F32)
        )
        data = jax.eval_shape(lambda: federated_token_batches(
            jax.random.PRNGKey(1), 2, 1, 512, cfg.vocab_size,
            heterogeneity=7,
        ))
        rnd = build_fused_round(
            make_adversarial_loss(cfg, remat=False),
            resolve_strategy("fedgda_gt"), 2, 2e-3,
        )
        compiled = rnd.lower(
            on_chip(params), on_chip(init_delta(cfg)), on_chip(data)
        ).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%(ssm_scan_(?:fwd|bwd))[.\d]* = .*tpu_custom_call",
                         text)
    assert sorted(kernels) == ["ssm_scan_bwd"] * 2 + ["ssm_scan_fwd"] * 2
    loops = [line for line in text.splitlines()
             if re.search(r"= \(.*\) while\(", line)]
    assert not [line for line in loops if "8192,1,16]" in line]
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < 4.5e9, total


def test_falcon_mamba_spmd_step_keeps_the_jnp_scan_on_a_v5e_mesh(
        topo, one_chip, monkeypatch):
    """`launch/steps.py`'s SPMD train step for falcon-mamba (1 layer,
    reduced widths that the kernels tile) on a 2x2 v5e mesh, d_inner
    split over 'model': the partitioner cannot split a Mosaic kernel, so
    the program traced under a mesh of four devices keeps the jnp scan
    and compiles."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_config("falcon-mamba-7b").reduced(),
                              num_layers=1)
    shape = ShapeConfig("tiny_train", seq_len=64, global_batch=4,
                        kind="train")
    with jax.enable_x64(False):
        jitted, specs_fn = build_train_step(cfg, mesh, num_local_steps=2,
                                            dtype=F32)
        sp = specs_fn(shape)
        compiled = jitted(shape).lower(sp["x"], sp["y"], sp["batch"]).compile()
    text = compiled.as_text()
    assert "ssm_scan" not in text
    assert re.search(r"all-reduce|all-gather|reduce-scatter", text)
