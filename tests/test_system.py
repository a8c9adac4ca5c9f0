"""End-to-end system behaviour tests: federated training on a real
(reduced) model, SPMD step builders on a host mesh, checkpointing, the
federated runner, and the data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint
from repro.configs import INPUT_SHAPES, ShapeConfig, get_config
from repro.core import make_fedgda_gt_round, make_local_sgda_round
from repro.data import federated_token_batches, partition_among_agents
from repro.fed import FederatedRunner, comm_table
from repro.launch.mesh import fed_axes, make_host_mesh, num_agents
from repro.launch.steps import (
    build_decode_step,
    build_prefill_step,
    build_train_step,
)
from repro.models import init_params, num_params, random_batch
from repro.problems.adversarial import (
    delta_projection,
    init_delta,
    make_adversarial_loss,
)

DT = jnp.float32

pytestmark = pytest.mark.slow  # multi-minute: deselect with -m "not slow"


@pytest.fixture(scope="module")
def small():
    cfg = get_config("gemma2-2b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg, DT)
    data = federated_token_batches(
        jax.random.PRNGKey(1), num_agents=4, per_agent_batch=2,
        seq_len=32, vocab_size=cfg.vocab_size, heterogeneity=11,
    )
    return cfg, params, data


# ------------------------------------------------------------ training e2e
class TestEndToEndTraining:
    def test_fedgda_gt_reduces_loss(self, small):
        cfg, params, data = small
        loss = make_adversarial_loss(cfg, remat=False)
        rnd = jax.jit(
            make_fedgda_gt_round(loss, 4, 5e-3, proj_y=delta_projection(1.0))
        )

        def gl(x, y):
            per = jax.vmap(loss, in_axes=(None, None, 0))(x, y, data)
            return jnp.mean(per)

        gl = jax.jit(gl)
        x, y = params, init_delta(cfg, DT)
        l0 = float(gl(x, y))
        for _ in range(15):
            x, y = rnd(x, y, data)
        l1 = float(gl(x, y))
        assert np.isfinite(l0) and np.isfinite(l1)
        assert l1 < l0 - 0.05, (l0, l1)

    def test_gt_tracks_global_not_local_descent(self, small):
        """Heterogeneous agents: after rounds of equal budget, the GT
        aggregate's GLOBAL loss should not be worse than Local SGDA's
        (whose aggregate drifts toward local optima)."""
        cfg, params, data = small
        loss = make_adversarial_loss(cfg, remat=False)
        K, eta = 8, 5e-3
        r_gt = jax.jit(
            make_fedgda_gt_round(loss, K, eta, proj_y=delta_projection(1.0))
        )
        r_ls = jax.jit(
            make_local_sgda_round(loss, K, eta, eta, proj_y=delta_projection(1.0))
        )

        def gl(x, y):
            per = jax.vmap(loss, in_axes=(None, None, 0))(x, y, data)
            return jnp.mean(per)

        gl = jax.jit(gl)
        y0 = init_delta(cfg, DT)
        xg, yg = params, y0
        xl, yl = params, y0
        for _ in range(10):
            xg, yg = r_gt(xg, yg, data)
            xl, yl = r_ls(xl, yl, data)
        assert float(gl(xg, yg)) <= float(gl(xl, yl)) + 0.02


# -------------------------------------------------------- SPMD step builders
class TestStepBuildersOnHostMesh:
    """The same builders the dry-run lowers on the production mesh must
    EXECUTE on a 1x1 host mesh (CPU) for a reduced config."""

    def test_train_step_executes(self):
        cfg = get_config("granite-8b").reduced()
        mesh = make_host_mesh(1, 1)
        shape = ShapeConfig("tiny_train", seq_len=32, global_batch=2, kind="train")
        with jax.set_mesh(mesh):
            jitted, specs_fn = build_train_step(
                cfg, mesh, num_local_steps=2, dtype=DT
            )
            sp = specs_fn(shape)
            m = num_agents(mesh, cfg.fed_mode)
            assert m == 1  # 1x1 mesh: single agent
            x = init_params(jax.random.PRNGKey(0), cfg, DT)
            y = init_delta(cfg, DT)
            batch = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), sp["batch"]
            )
            x1, y1 = jitted(shape)(x, y, batch)
            assert all(
                bool(jnp.all(jnp.isfinite(u))) for u in jax.tree.leaves(x1)
            )

    def test_quantized_train_step_threads_state(self):
        """quantized_gt rides the same stateful path as partial_gt /
        compressed_gt: rounding RNG + error-feedback buffers as a 4th
        replicated step input."""
        import dataclasses as _dc

        cfg = _dc.replace(
            get_config("granite-8b").reduced(), quantization_bits=8
        )
        mesh = make_host_mesh(1, 1)
        shape = ShapeConfig("tiny_train", seq_len=32, global_batch=2, kind="train")
        with jax.set_mesh(mesh):
            jitted, specs_fn = build_train_step(
                cfg, mesh, algorithm="quantized_gt", num_local_steps=2, dtype=DT
            )
            sp = specs_fn(shape)
            assert "state" in sp  # stateful: rounding RNG (+ EF buffers)
            x = init_params(jax.random.PRNGKey(0), cfg, DT)
            y = init_delta(cfg, DT)
            batch = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), sp["batch"]
            )
            state = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), sp["state"]
            )
            x1, y1, state1 = jitted(shape)(x, y, batch, state)
            assert all(
                bool(jnp.all(jnp.isfinite(u))) for u in jax.tree.leaves(x1)
            )
            assert jax.tree.structure(state1) == jax.tree.structure(state)

    def test_elastic_train_step_executes(self):
        """The membership-aware elastic round as an SPMD step: schedule
        inputs (tracker table, weights, budgets, active) ride along and
        the round executes for a stateful strategy."""
        import dataclasses as _dc

        from repro.launch.steps import build_elastic_train_step

        cfg = _dc.replace(
            get_config("granite-8b").reduced(), quantization_bits=8
        )
        mesh = make_host_mesh(1, 1)
        shape = ShapeConfig("tiny_train", seq_len=32, global_batch=2,
                            kind="train")
        with jax.set_mesh(mesh):
            jitted, specs_fn = build_elastic_train_step(
                cfg, mesh, algorithm="quantized_gt", num_local_steps=2,
                dtype=DT,
            )
            sp = specs_fn(shape)
            m = num_agents(mesh, cfg.fed_mode)
            x = init_params(jax.random.PRNGKey(0), cfg, DT)
            y = init_delta(cfg, DT)
            z = lambda t: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), t
            )
            x1, y1, state1, tracker1 = jitted(shape)(
                x, y, z(sp["batch"]), z(sp["state"]), z(sp["tracker"]),
                jnp.full((m,), 1.0 / m, jnp.float32),
                jnp.full((m,), 2, jnp.int32),
                jnp.ones((m,), bool),
                jnp.ones((m,), bool),
            )
            assert all(
                bool(jnp.all(jnp.isfinite(u))) for u in jax.tree.leaves(x1)
            )
            assert set(tracker1) == {"gx", "gy"}

    def test_prefill_and_decode_execute(self):
        cfg = get_config("starcoder2-7b").reduced()
        mesh = make_host_mesh(1, 1)
        with jax.set_mesh(mesh):
            shape = ShapeConfig("tiny_prefill", seq_len=32, global_batch=2,
                                kind="prefill")
            jit_p, specs_p = build_prefill_step(cfg, mesh, dtype=DT)
            params = init_params(jax.random.PRNGKey(0), cfg, DT)
            batch = random_batch(jax.random.PRNGKey(1), cfg, 2, 32, DT)
            sp = specs_p(shape)
            caches = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), sp["caches"]
            )
            logits, caches = jit_p(shape)(params, batch, caches)
            assert logits.shape[0] == 2 and bool(jnp.all(jnp.isfinite(logits)))

            dshape = ShapeConfig("tiny_decode", seq_len=32, global_batch=2,
                                 kind="decode")
            jit_d, _ = build_decode_step(cfg, mesh, dtype=DT)
            tok = jnp.zeros((2, 1), jnp.int32)
            logits2, caches = jit_d(dshape)(
                params, caches, tok, jnp.int32(32 - 1)
            )
            assert logits2.shape == (2, 1, cfg.vocab_size)
            assert bool(jnp.all(jnp.isfinite(logits2)))

    def test_fed_axes_modes(self):
        mesh = make_host_mesh(1, 1)
        assert fed_axes(mesh, "A") == ("data",)
        assert fed_axes(mesh, "B") == ()
        assert num_agents(mesh, "B") == 1


# ------------------------------------------------------------- checkpointing
class TestCheckpointing:
    def test_roundtrip_exact(self, tmp_path, small):
        cfg, params, _ = small
        tree = {"x": params, "y": init_delta(cfg, DT), "meta": jnp.int32(7)}
        path = save_checkpoint(str(tmp_path), 3, tree)
        back = restore_checkpoint(path)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_selection(self, tmp_path):
        for step in (1, 12, 5):
            save_checkpoint(str(tmp_path), step, {"v": jnp.ones(3)})
        step, path = latest_checkpoint(str(tmp_path))
        assert step == 12 and os.path.exists(path)

    def test_latest_none_for_missing_dir(self, tmp_path):
        assert latest_checkpoint(str(tmp_path / "nope")) is None


# ------------------------------------------------------------ fed runtime
class TestFederatedRunner:
    def test_runner_history_and_checkpoints(self, tmp_path):
        from repro.problems import make_quadratic_problem

        prob = make_quadratic_problem(
            jax.random.PRNGKey(0), dim=8, num_samples=30, num_agents=4
        )
        rnd = make_fedgda_gt_round(prob.loss, 5, 1e-3)
        runner = FederatedRunner(
            rnd,
            prob.agent_data,
            metric_fn=lambda x, y: {"gap": jnp.sum(x**2) + jnp.sum(y**2)},
            checkpoint_dir=str(tmp_path),
            checkpoint_every=10,
        )
        x0 = jnp.ones(8)
        runner.run(x0, x0, num_rounds=20)
        assert len(runner.history) == 20
        series = runner.metric_series("gap")
        assert series.shape == (20,)
        assert latest_checkpoint(str(tmp_path))[0] == 20

    def test_comm_table(self):
        x, y = jnp.zeros(1000), jnp.zeros(10)
        t = comm_table(x, y, 10, {"fedgda_gt": 50, "local_sgda": 5000})
        assert t["fedgda_gt"]["total_bytes"] < t["local_sgda"]["total_bytes"]


# ------------------------------------------------------------- data pipeline
class TestDataPipeline:
    def test_federated_batches_shape_and_heterogeneity(self):
        d = federated_token_batches(
            jax.random.PRNGKey(0), num_agents=4, per_agent_batch=3,
            seq_len=16, vocab_size=97, heterogeneity=5,
        )
        assert d["tokens"].shape == (4, 3, 16)
        assert d["labels"].shape == (4, 3, 16)
        # heterogeneity shifts marginals: agent histograms must differ
        h0 = np.bincount(np.asarray(d["tokens"][0]).ravel(), minlength=97)
        h3 = np.bincount(np.asarray(d["tokens"][3]).ravel(), minlength=97)
        assert np.argmax(h0) != np.argmax(h3)

    def test_partition_among_agents(self):
        data = {"a": jnp.arange(12).reshape(12, 1)}
        part = partition_among_agents(data, 4)
        assert part["a"].shape == (4, 3, 1)
        np.testing.assert_array_equal(
            np.asarray(part["a"].reshape(12, 1)), np.asarray(data["a"])
        )
