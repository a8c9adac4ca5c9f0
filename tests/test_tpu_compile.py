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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.data import federated_token_batches
from repro.fed.strategies import resolve_strategy
from repro.kernels import ref
from repro.kernels.compress_correction import compress_correction_2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gt_update import gt_update_2d
from repro.kernels.pack_payload import pack_payload_2d, unpack_payload_2d
from repro.kernels.ssm_scan import ssm_scan
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
    # falcon-mamba-7b: d_inner 8192, state 16.  The state axis pads to
    # 128 lanes in VMEM, so the default chunk of 64 overflows the 16 MiB
    # of scoped VMEM by 128 KiB; 32 fits
    "ssm_scan": (
        lambda a, b, c: ssm_scan(a, b, c, chunk=32, interpret=False),
        [((1024, 8192, 16), F32), ((1024, 8192, 16), F32),
         ((1024, 16), F32)],
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
