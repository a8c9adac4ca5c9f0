"""Async runtime + multi-host packed-payload gather (8-device CPU emulation).

Acceptance contracts of the phase-dispatched runtimes:

  * `AsyncFederatedRunner` matches `FederatedRunner` iterates to fp
    tolerance for every scenario strategy on the 8-device emulated mesh —
    including the stateful ones, because every random draw happens once,
    server-side, through the same strategy code path; per-agent
    error-feedback state SHARDS across the agent devices instead of
    replicating, and still ends up equal to the sync runner's;
  * `MultiHostRunner` gathers the REAL packed buffers: the per-round
    gathered payload bytes equal both the LeafSpec expectation and the
    m-agent payload share of `transport.measured_bytes_per_round`;
  * `build_gather_decode_step`'s lowered all-gather collective bytes
    equal that same payload (the census the dry-run `--runtime async`
    artifacts carry, gated by comm_collectives --check-async).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fed import AsyncFederatedRunner, FederatedRunner
from repro.fed.strategies import (
    CompressedGT,
    FullSync,
    GradientTracking,
    LocalOnly,
    PartialParticipation,
    QuantizedGT,
)
from repro.fed.transport import dense_payload_bytes, measured_bytes_per_round
from repro.launch.multihost import (
    MultiHostRunner,
    build_gather_decode_step,
    expected_gather_bytes,
    init_distributed,
)
from repro.problems import make_quadratic_problem

pytestmark = pytest.mark.multihost

ETA, K, ROUNDS = 1e-3, 4, 6
DIM, M = 16, 8

SCENARIOS = {
    "full_sync": FullSync(),
    "local_only": LocalOnly(),
    "gradient_tracking": GradientTracking(),
    "partial_gt": PartialParticipation(participation=0.5, seed=0),
    "compressed_gt": CompressedGT(compression_ratio=0.25, wire_transport=True),
    "quantized_gt": QuantizedGT(bits=8, wire_transport=True),
}


@pytest.fixture(scope="module")
def prob():
    return make_quadratic_problem(
        jax.random.PRNGKey(0), dim=DIM, num_samples=60, num_agents=M
    )


x0 = jnp.ones(DIM)
y0 = -jnp.ones(DIM)


class TestAsyncRunnerParity:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_matches_sync_runner_iterates(self, prob, name, fed_devices):
        strategy = SCENARIOS[name]
        sync = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA
        )
        xs, ys = sync.run(x0, y0, ROUNDS)
        runner = AsyncFederatedRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        xa, ya = runner.run(x0, y0, ROUNDS)
        assert runner._n_shards == M  # one agent per emulated device
        # identity-transform GT forms each correction on its shard; the
        # compressors transform the whole stack server-side
        assert runner._shard_corr is (
            name in ("gradient_tracking", "partial_gt")
        )
        np.testing.assert_allclose(
            np.asarray(xa), np.asarray(xs), rtol=1e-9, atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(ya), np.asarray(ys), rtol=1e-9, atol=1e-12
        )

    def test_error_feedback_state_shards_and_matches_sync(
        self, prob, fed_devices
    ):
        strategy = QuantizedGT(bits=8, wire_transport=True)
        sync = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA
        )
        sync.run(x0, y0, ROUNDS)
        runner = AsyncFederatedRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        runner.run(x0, y0, ROUNDS)
        # EF buffers live as per-agent slices on the shard devices...
        assert runner._sharded_keys == ("ex", "ey")
        for i, shard in enumerate(runner._shard_state):
            assert set(shard) == {"ex", "ey"}
            leaf = jax.tree.leaves(shard["ex"])[0]
            assert leaf.shape[0] == M // runner._n_shards
            assert leaf.devices() == {runner._shard_devices[i]}
        # ...the RNG key stays server-side...
        assert set(runner._server_state) == {"key"}
        # ...and gathered back together they equal the sync state
        gathered = runner._gather_state()
        for k in ("ex", "ey", "key"):
            for a, b in zip(
                jax.tree.leaves(gathered[k]),
                jax.tree.leaves(sync._state[k]),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12
                )

    def test_history_and_metric_series(self, prob, fed_devices):
        runner = AsyncFederatedRunner(
            prob.loss, GradientTracking(), prob.agent_data, K, ETA,
            devices=fed_devices,
            metric_fn=lambda x, y: {"gap": jnp.sum(x**2)},
        )
        runner.run(x0, y0, 3)
        assert runner.metric_series("gap").shape == (3,)
        with pytest.raises(ValueError, match="available metric keys"):
            runner.metric_series("loss")

    def test_caller_arrays_survive_donation(self, prob, fed_devices):
        """The donated broadcast buffers must never alias caller arrays:
        x0/y0 stay usable after (and between) runs."""
        runner = AsyncFederatedRunner(
            prob.loss, GradientTracking(), prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        runner.run(x0, y0, 2)
        runner.run(x0, y0, 2)  # same inputs again: would throw if deleted
        assert bool(jnp.all(jnp.isfinite(x0)))


class TestMultiHostGather:
    @pytest.mark.parametrize(
        "strategy",
        [
            CompressedGT(compression_ratio=0.25, wire_transport=True),
            QuantizedGT(bits=8, wire_transport=True),
            QuantizedGT(bits=4, ratio=0.25, wire_transport=True),
        ],
        ids=["topk25", "q8", "q4_top25"],
    )
    def test_gathered_bytes_equal_measured_payload(
        self, prob, strategy, fed_devices
    ):
        runner = MultiHostRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        x1, y1 = runner.run(x0, y0, 2)
        assert bool(jnp.all(jnp.isfinite(x1)))
        assert len(runner.wire_log) == 2
        gathered = runner.wire_log[-1]["gathered_payload_bytes"]
        # (a) the LeafSpec expectation
        assert gathered == expected_gather_bytes(strategy, x0, y0, M)
        # (b) the m-agent payload share of measured_bytes_per_round
        meas = measured_bytes_per_round(
            strategy, x0, y0, K, include_headers=False
        )
        payload_share = (meas - 2 * dense_payload_bytes((x0, y0))) // 2
        assert gathered == M * payload_share

    def test_exact_gt_multihost_matches_sync(self, prob, fed_devices):
        """No randomness, exact correction: the multi-host schedule must
        agree with the fused round to fp tolerance."""
        sync = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, K, ETA
        )
        xs, ys = sync.run(x0, y0, ROUNDS)
        runner = MultiHostRunner(
            prob.loss, GradientTracking(), prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        xm, ym = runner.run(x0, y0, ROUNDS)
        np.testing.assert_allclose(
            np.asarray(xm), np.asarray(xs), rtol=1e-9, atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(ym), np.asarray(ys), rtol=1e-9, atol=1e-12
        )

    def test_rejects_payload_free_strategies(self, prob):
        with pytest.raises(ValueError, match="gathers correction payloads"):
            MultiHostRunner(prob.loss, LocalOnly(), prob.agent_data, K, ETA)
        with pytest.raises(ValueError, match="full-participation"):
            MultiHostRunner(
                prob.loss,
                PartialParticipation(participation=0.5),
                prob.agent_data,
                K,
                ETA,
            )

    def test_init_distributed_noop_single_process(self, monkeypatch):
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        assert init_distributed() is False


class TestGatherDecodeCensus:
    def test_all_gather_bytes_equal_payload(self, fed_devices):
        from repro.launch.hlo_census import HloCensus

        mesh = jax.make_mesh((8,), ("data",), devices=fed_devices)
        strategy = QuantizedGT(bits=8, wire_transport=True)
        jitted, args, expected = build_gather_decode_step(
            strategy, x0, y0, mesh, ("data",)
        )
        compiled = jitted.lower(*args).compile()
        census = HloCensus(compiled.as_text()).summary()[
            "collectives_executed"
        ]
        assert census.get("all-gather", {}).get("bytes", 0) == expected
        assert expected == expected_gather_bytes(strategy, x0, y0, 8)

    def test_check_async_gate(self, tmp_path, fed_devices):
        """benchmarks/comm_collectives.check_async passes a faithful
        record and fails a drifted one."""
        import json

        from benchmarks.comm_collectives import check_async

        rec = {
            "gather_census": {"all-gather": {"count": 4, "bytes": 384}},
            "expected_gather_bytes": 384,
            "wire": {
                "measured_bytes_per_round": 352,
                "payload_share_per_agent": 48,
                "num_agents": 8,
            },
        }
        with open(tmp_path / "a__async.json", "w") as f:
            json.dump(rec, f)
        assert check_async(str(tmp_path)) == 0
        rec["gather_census"]["all-gather"]["bytes"] = 9999
        with open(tmp_path / "b__async.json", "w") as f:
            json.dump(rec, f)
        assert check_async(str(tmp_path)) == 1
