"""The plain reference builds the program's problem from the seed alone
and agrees with the program's model at a small size."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference

import tiny


def _program(c, **cuts):
    cfg = harness.program_config(c)
    return dataclasses.replace(cfg, **cuts) if cuts else cfg


@pytest.mark.parametrize("kind", ["dense", "mamba"])
def test_seed_gives_the_programs_weights_and_tokens(kind):
    from repro.data import federated_token_batches
    from repro.models import init_params

    c = tiny.cell(kind)["config"]
    cfg = _program(c)
    pk, dk = reference.seed_keys(2**40 + 3)
    mine = reference.init_params(pk, c)
    theirs = init_params(pk, cfg, jnp.float32)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b)
    d1 = reference.make_data(dk, 3, 2, 16, c["vocab_size"], 7)
    d2 = federated_token_batches(dk, 3, 2, 16, c["vocab_size"], heterogeneity=7)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(d1[k], d2[k])


def test_seeds_beyond_32_bits_do_not_alias():
    a = reference.seed_keys(5)
    b = reference.seed_keys(2**32 + 5)
    assert not np.array_equal(a[0], b[0])


@pytest.mark.parametrize("kind,kv", [("dense", 2), ("mamba", None)])
def test_loss_and_gradients_match_the_programs_model(kind, kv):
    from repro.problems.adversarial import make_adversarial_loss

    c = dict(tiny.cell(kind)["config"])
    cuts = {}
    if kv:  # grouped-query attention: 2 query heads per KV head
        c["num_key_value_heads"] = kv
        cuts["num_kv_heads"] = kv
    cfg = _program(tiny.cell(kind)["config"], **cuts)
    pk, dk = reference.seed_keys(11)
    x = reference.init_params(pk, c)
    y = {"delta": 0.1 * jax.random.normal(dk, (c["hidden_size"],))}
    batch = jax.tree.map(lambda u: u[0], reference.make_data(
        dk, 1, 2, 64, c["vocab_size"], 0))
    prog_loss = make_adversarial_loss(cfg, remat=False)
    ref_loss = lambda x, y, b: reference.agent_loss(x, y, b, c)
    assert float(ref_loss(x, y, batch)) == pytest.approx(
        float(prog_loss(x, y, batch)), rel=1e-5)
    gp = jax.grad(prog_loss, argnums=(0, 1))(x, y, batch)
    gr = jax.grad(ref_loss, argnums=(0, 1))(x, y, batch)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6 * float(jnp.max(jnp.abs(b))))
