"""The observability contract: telemetry, probes and the run ledger.

The acceptance-grade facts pinned here (see tests/README.md for the
event schema):

  * telemetry is bitwise-free when disabled: a runner given
    `telemetry=None` — and the SAME runner after flipping the sink on —
    produces bitwise-identical iterates for all six strategy families
    across the sync runner (plain and elastic), the async runner, and
    the sparse engine (dense-fallback and genuinely-sparse paths); the
    sink never enters a jitted program, so enabling it cannot perturb a
    single bit;
  * the sink's spans are on the profiler's clock: a `jax.profiler`
    trace of an enabled run holds one `round` host span per round and
    one span per phase dispatch, which `chipbench/trace.py load` reads;
  * the invariant probes are pure functions that read ~fp-reduction
    noise when the math is right: `gt_residual` over the tracker-table
    corrections, `tracker_drift` of the SparseTracker running sums, EF
    residual norms, priced-vs-measured bytes — and they AGREE across
    the sync-elastic, async-elastic and forced-sparse runtimes on a
    shared seed;
  * "wire_bytes" counters are byte truth: on a scheduled run each
    round's value equals `sim.schedule_bytes` exactly (per-active-agent
    payload x n_active); unscheduled, per_agent equals
    `transport.measured_bytes_per_round` as-is;
  * `wire_report` is active-set-aware: after (or with) a schedule it
    adds the `scheduled_*` keys priced by `sim.schedule_bytes`, and a
    static-full schedule adds nothing (the run was the legacy path);
  * the run ledger round-trips: every emitted event lands in
    events.jsonl verbatim, and the manifest records the seed-fold
    stream constants and the schedule digest (`summary_trace`);
  * `metric_series` on an EMPTY history raises the ValueError naming
    the available keys instead of returning a silent empty array;
  * `peak_memory` moved to `repro.obs` with `benchmarks.common`
    re-exporting the same function object, and a sink records the
    measurement as a "peak_memory" counter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import grad_xy
from repro.fed import (
    AsyncFederatedRunner,
    CompressedGT,
    FederatedRunner,
    FullSync,
    GradientTracking,
    LocalOnly,
    PartialParticipation,
    QuantizedGT,
)
from repro.fed.noise import NOISE_STREAM
from repro.fed.transport import measured_bytes_per_round
from repro.obs import RunLedger, Telemetry, maybe_span, peak_memory, probes
from repro.obs import run_manifest
from repro.problems import make_quadratic_problem, quadratic_minimax_point
from repro.sim import (
    ArrayDataSource,
    Population,
    SparseElasticEngine,
    UniformActiveSubset,
    UniformStragglers,
    make_population,
    per_agent_bytes,
    schedule_bytes,
)
from repro.sim.schedule import AVAILABILITY_STREAM

pytestmark = pytest.mark.obs

ETA = 1e-4
DIM, M, T = 16, 8, 5
SEED = 0

STRATEGIES = [
    ("full_sync", FullSync(), 1),
    ("local_only", LocalOnly(), 5),
    ("gradient_tracking", GradientTracking(), 5),
    ("partial_participation", PartialParticipation(participation=0.5, seed=0), 5),
    ("compressed_gt", CompressedGT(compression_ratio=0.25, seed=0), 5),
    ("quantized_gt", QuantizedGT(bits=8, seed=0), 5),
]
IDS = [s[0] for s in STRATEGIES]

x0 = jnp.ones(DIM)
y0 = -jnp.ones(DIM)

#: a sink with every emission path on: probes sampled each round, a gap
#: oracle, phase bookkeeping — everything except a profiler trace (its
#: own test below) and a ledger (its own round-trip test below)
ALL_PROBES = (
    "gt_residual", "tracker_drift", "ef_residual", "priced_vs_measured",
    "duality_gap",
)


@pytest.fixture(scope="module")
def prob():
    return make_quadratic_problem(
        jax.random.PRNGKey(0), dim=DIM, num_samples=40, num_agents=M
    )


def _full_telemetry(prob):
    xs, ys = quadratic_minimax_point(prob)
    from repro.core import tree_sq_dist

    return Telemetry(
        probes=ALL_PROBES,
        gap_fn=lambda x, y: tree_sq_dist(x, xs) + tree_sq_dist(y, ys),
    )


def _fresh_state(strategy, x, y, m):
    return (
        strategy.init_state(x, y, m)
        if getattr(strategy, "stateful", False)
        else None
    )


def _flaky_schedule(K, rounds=T):
    return make_population("flaky", M).schedule(SEED, rounds, K)


def _sparse_schedule(K, rounds=T):
    pop = Population(
        M,
        UniformActiveSubset(size=4),
        UniformStragglers(p_straggle=0.5, min_frac=0.4),
    )
    return pop.sparse_schedule(SEED, rounds, K)


# ------------------------------------------------- disabled == bitwise pin
class TestBitwisePins:
    """telemetry=None vs an enabled sink (probes, gap oracle and all) on
    the SAME compiled runner: iterates must be bitwise identical — the
    sink is host-side only, so the jitted programs cannot differ."""

    @pytest.mark.parametrize("name,strategy,K", STRATEGIES, ids=IDS)
    def test_sync_plain(self, prob, name, strategy, K):
        runner = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA
        )
        xa, ya = runner.run(x0, y0, T, state=_fresh_state(strategy, x0, y0, M))
        runner.telemetry = _full_telemetry(prob)
        xb, yb = runner.run(x0, y0, T, state=_fresh_state(strategy, x0, y0, M))
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
        assert len(runner.telemetry.series("span", "round")) == T

    @pytest.mark.parametrize("name,strategy,K", STRATEGIES, ids=IDS)
    def test_sync_elastic(self, prob, name, strategy, K):
        sched = _flaky_schedule(K)
        runner = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA
        )
        xa, ya = runner.run(
            x0, y0, T, schedule=sched,
            state=_fresh_state(strategy, x0, y0, M),
        )
        runner.telemetry = _full_telemetry(prob)
        xb, yb = runner.run(
            x0, y0, T, schedule=sched,
            state=_fresh_state(strategy, x0, y0, M),
        )
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))

    @pytest.mark.multihost
    @pytest.mark.parametrize("name,strategy,K", STRATEGIES, ids=IDS)
    def test_async(self, prob, name, strategy, K, fed_devices):
        # two runners (async shard state initializes once per runner);
        # same devices, same programs — only the sink differs
        off = AsyncFederatedRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        xa, ya = off.run(x0, y0, T)
        on = AsyncFederatedRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices, telemetry=_full_telemetry(prob),
        )
        xb, yb = on.run(x0, y0, T)
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
        assert len(on.telemetry.series("span", "round")) == T

    @pytest.mark.parametrize("name,strategy,K", STRATEGIES, ids=IDS)
    @pytest.mark.parametrize("fallback", [True, False],
                             ids=["dense-fallback", "sparse"])
    def test_sparse_engine(self, prob, name, strategy, K, fallback):
        sched = _sparse_schedule(K)
        kw = {} if fallback else {"dense_fallback_max_m": 0}

        def build(tm):
            return SparseElasticEngine(
                prob.loss, strategy, ArrayDataSource(prob.agent_data),
                K, ETA, telemetry=tm, **kw,
            )

        xa, ya = build(None).run(x0, y0, sched)
        tm = _full_telemetry(prob)
        xb, yb = build(tm).run(x0, y0, sched)
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
        fb = tm.series("event", "dense_fallback")
        assert len(fb) == 1 and fb[0]["value"] is (True if fallback else False)


# --------------------------------------------------- spans on the profiler
def _trace_module():
    """`chipbench.trace`, from the benchmark package beside `src/`."""
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import trace

    return trace


PHASES = ("broadcast", "exchange_corrections", "local_steps", "aggregate")


@pytest.mark.multihost
class TestProfilerSpans:
    def test_round_and_phase_spans_in_the_trace(self, prob, fed_devices,
                                                tmp_path):
        """Every round of an async run profiled: the trace's host spans
        hold `round` once per round, in order, and each phase span of
        the runtime once per round, inside its round."""
        trace = _trace_module()
        tm = Telemetry(profile_dir=str(tmp_path), profile_rounds=range(T))
        runner = AsyncFederatedRunner(
            prob.loss, GradientTracking(), prob.agent_data, 4, ETA,
            devices=fed_devices, telemetry=tm,
        )
        runner.run(x0, y0, T)
        tm.close()
        host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
        rounds = sorted((s, s + d) for n, s, d in host if n == "round")
        assert len(rounds) == T
        assert len(tm.series("event", "profile_trace")) == 1
        for phase in PHASES:
            spans = [(s, s + d) for n, s, d in host if n == phase]
            assert len(spans) == T == len(tm.series("span", phase))
            for (a, b), (lo, hi) in zip(sorted(spans), rounds):
                assert lo <= a <= b <= hi

    def test_consecutive_rounds_share_one_trace(self, prob, tmp_path):
        """Rounds 1 and 2 of 4 profiled: one trace holding their two
        `round` spans, stopped after round 2."""
        trace = _trace_module()
        tm = Telemetry(profile_dir=str(tmp_path), profile_rounds=(1, 2))
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, 2, ETA,
            telemetry=tm,
        )
        runner.run(x0, y0, 4)
        assert not tm._profiling
        assert len(tm.series("event", "profile_trace")) == 1
        host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
        assert [n for n, _, _ in host if n == "round"] == ["round"] * 2


# ------------------------------------------------------------- probe units
class TestProbeFunctions:
    def test_anchor_corrections_satisfy_gt_invariant(self, prob):
        cx, cy = probes.anchor_corrections(
            grad_xy(prob.loss), x0, y0, prob.agent_data
        )
        assert probes.gt_residual(cx, cy) < 1e-10

    def test_table_corrections_and_drift(self, prob):
        g = jax.vmap(grad_xy(prob.loss), in_axes=(None, None, 0))(
            x0, y0, prob.agent_data
        )
        cx, cy = probes.corrections_from_table(g.gx, g.gy)
        assert probes.gt_residual(cx, cy) < 1e-10
        colsum = jax.tree.map(lambda u: jnp.sum(u, axis=0), (g.gx, g.gy))
        assert probes.tracker_drift(g.gx, g.gy, *colsum) == 0.0
        # a perturbed running sum reads as drift
        off = jax.tree.map(lambda u: u + 1.0, colsum[0])
        assert probes.tracker_drift(g.gx, g.gy, off, colsum[1]) > 1.0

    def test_ef_residual_norms(self):
        assert probes.ef_residual_norms(None) == {}
        assert probes.ef_residual_norms({"rng": 0}) == {}
        norms = probes.ef_residual_norms(
            {"ex": jnp.full((3,), 2.0), "ey": jnp.zeros((3,))}
        )
        np.testing.assert_allclose(norms["ex"], np.sqrt(12.0))
        assert norms["ey"] == 0.0

    def test_priced_vs_measured(self, prob):
        pv = probes.priced_vs_measured(GradientTracking(), x0, y0, 4)
        assert pv["priced"] == pv["measured"] > 0

    def test_duality_gap_uses_oracle(self):
        assert probes.duality_gap(lambda x, y: 7.5, x0, y0) == 7.5


# -------------------------------------------- probe parity across runtimes
class TestProbeParity:
    """The same pure probes over the state each runtime holds, on a
    shared seed: the GT invariant must read ~fp noise everywhere, and
    the priced-vs-measured account must be the SAME dict — a mismatch
    localizes the faulty layer, not the faulty runner."""

    K = 5

    def _run_sync(self, prob):
        tm = _full_telemetry(prob)
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, self.K, ETA,
            telemetry=tm,
        )
        runner.run(x0, y0, T, schedule=_flaky_schedule(self.K))
        return tm

    def test_sync_elastic_probes(self, prob):
        tm = self._run_sync(prob)
        res = tm.probe_series("gt_residual")
        assert len(res) == T and max(res) < 1e-8
        assert tm.probe_series("duality_gap")

    @pytest.mark.multihost
    def test_async_elastic_agrees_with_sync(self, prob, fed_devices):
        sync_tm = self._run_sync(prob)
        tm = _full_telemetry(prob)
        runner = AsyncFederatedRunner(
            prob.loss, GradientTracking(), prob.agent_data, self.K, ETA,
            devices=fed_devices, telemetry=tm,
        )
        runner.run(x0, y0, T, schedule=_flaky_schedule(self.K))
        res = tm.probe_series("gt_residual")
        assert len(res) == T and max(res) < 1e-8
        assert (
            tm.probe_series("priced_vs_measured")
            == sync_tm.probe_series("priced_vs_measured")
        )

    def test_forced_sparse_agrees(self, prob):
        sync_tm = self._run_sync(prob)
        tm = _full_telemetry(prob)
        eng = SparseElasticEngine(
            prob.loss, GradientTracking(), ArrayDataSource(prob.agent_data),
            self.K, ETA, dense_fallback_max_m=0, telemetry=tm,
        )
        eng.run(x0, y0, _sparse_schedule(self.K))
        res = tm.probe_series("gt_residual")
        assert len(res) == T and max(res) < 1e-8
        drift = tm.probe_series("tracker_drift")
        assert len(drift) == T and max(drift) < 1e-8
        assert (
            tm.probe_series("priced_vs_measured")
            == sync_tm.probe_series("priced_vs_measured")
        )

    def test_ef_residual_probe_sees_compressor_state(self, prob):
        tm = _full_telemetry(prob)
        runner = FederatedRunner.from_strategy(
            prob.loss, CompressedGT(compression_ratio=0.25, seed=0),
            prob.agent_data, self.K, ETA, telemetry=tm,
        )
        runner.run(x0, y0, T)
        norms = tm.probe_series("ef_residual")
        assert len(norms) == T
        # top-k residuals are non-zero after the first compression
        assert norms[-1]["ex"] > 0.0


# ------------------------------------------------------------- wire truth
class TestWireCounters:
    def test_scheduled_counter_equals_schedule_bytes(self, prob):
        K = 5
        strategy = GradientTracking()
        sched = _flaky_schedule(K)
        tm = Telemetry()
        runner = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA, telemetry=tm,
        )
        runner.run(x0, y0, T, schedule=sched)
        counters = tm.series("counter", "wire_bytes")
        totals = schedule_bytes(strategy, x0, y0, K, sched)
        assert [e["value"] for e in counters] == [int(v) for v in totals[:T]]
        pa = per_agent_bytes(strategy, x0, y0, K)
        assert all(e["per_agent"] == pa for e in counters)
        assert [e["value"] // pa for e in counters] == [
            e["n_active"] for e in counters
        ]

    def test_unscheduled_counter_is_measured_times_m(self, prob):
        K = 5
        strategy = CompressedGT(compression_ratio=0.25, seed=0)
        tm = Telemetry(probes=("priced_vs_measured",))
        runner = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA, telemetry=tm,
        )
        runner.run(x0, y0, T)
        meas = int(measured_bytes_per_round(strategy, x0, y0, K))
        for e in tm.series("counter", "wire_bytes"):
            assert e["per_agent"] == meas and e["value"] == meas * M
        (pv,) = tm.probe_series("priced_vs_measured")
        assert pv["measured"] == meas

    def test_wire_report_is_schedule_aware(self, prob):
        K = 5
        strategy = GradientTracking()
        sched = _flaky_schedule(K)
        runner = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, ETA
        )
        runner.run(x0, y0, T, schedule=sched)
        # remembered from run(..., schedule=...) — no need to re-pass
        rep = runner.wire_report(x0, y0, K)
        totals = schedule_bytes(strategy, x0, y0, K, sched)
        assert rep["scheduled_per_agent_bytes"] == per_agent_bytes(
            strategy, x0, y0, K
        )
        assert rep["scheduled_total_bytes"] == int(np.sum(totals))
        assert rep["scheduled_mean_bytes_per_round"] == pytest.approx(
            float(np.mean(totals))
        )
        # passing the schedule explicitly is the same account
        assert runner.wire_report(x0, y0, K, schedule=sched) == rep

    def test_wire_report_static_full_has_no_scheduled_keys(self, prob):
        K = 5
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, K, ETA
        )
        sched = make_population("stable", M).schedule(SEED, T, K)
        runner.run(x0, y0, T, schedule=sched)
        rep = runner.wire_report(x0, y0, K)
        assert set(rep) == {"bytes_per_round", "measured_bytes_per_round"}

    @pytest.mark.multihost
    def test_async_wire_report_mirrors_sync(self, prob, fed_devices):
        K = 5
        strategy = GradientTracking()
        sched = _flaky_schedule(K)
        runner = AsyncFederatedRunner(
            prob.loss, strategy, prob.agent_data, K, ETA,
            devices=fed_devices,
        )
        runner.run(x0, y0, T, schedule=sched)
        rep = runner.wire_report(x0, y0, K)
        totals = schedule_bytes(strategy, x0, y0, K, sched)
        assert rep["scheduled_total_bytes"] == int(np.sum(totals))


# ------------------------------------------------------- multihost absorbs
@pytest.mark.multihost
class TestMultiHostTelemetry:
    def test_wire_log_absorbed_and_bitwise(self, prob, fed_devices):
        from repro.launch.multihost import MultiHostRunner

        strategy = CompressedGT(compression_ratio=0.25, wire_transport=True)
        off = MultiHostRunner(
            prob.loss, strategy, prob.agent_data, 4, ETA,
            devices=fed_devices,
        )
        xa, ya = off.run(x0, y0, 2)
        tm = Telemetry()
        on = MultiHostRunner(
            prob.loss, strategy, prob.agent_data, 4, ETA,
            devices=fed_devices, telemetry=tm,
        )
        xb, yb = on.run(x0, y0, 2)
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
        # wire_log stays; the sink absorbs it as counters
        gathered = [
            e["value"]
            for e in tm.series("counter", "gathered_payload_bytes")
        ]
        assert gathered == [
            w["gathered_payload_bytes"] for w in on.wire_log
        ]
        rounds = tm.series("span", "round")
        assert [e["runtime"] for e in rounds] == ["multihost"] * 2
        for phase in ("broadcast", "exchange_corrections", "local_steps",
                      "aggregate"):
            assert len(tm.series("span", phase)) == 2


# ---------------------------------------------------------- sparse events
class TestSparseEvents:
    def test_realign_and_active_set_events(self, prob):
        K = 5
        tm = Telemetry()
        eng = SparseElasticEngine(
            prob.loss, GradientTracking(), ArrayDataSource(prob.agent_data),
            K, ETA, dense_fallback_max_m=0, telemetry=tm,
        )
        eng.run(x0, y0, _sparse_schedule(K))
        rounds = tm.series("span", "round")
        assert [e["runtime"] for e in rounds] == ["sparse"] * T
        # the fixed-size sampler keeps 4 agents active every round
        assert all(e["n_active"] == 4 for e in rounds)
        realigns = tm.series("event", "realign")
        assert len(realigns) == T - 1  # every round after the first
        assert all(0 <= e["n_continuing"] <= 4 for e in realigns)


# ---------------------------------------------------------- ledger + seeds
class TestRunLedger:
    def test_events_round_trip_jsonl(self, prob, tmp_path):
        import json

        d = str(tmp_path / "ledger")
        ledger = RunLedger(d)
        tm = Telemetry(ledger=ledger, probes=("priced_vs_measured",))
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, 4, ETA,
            telemetry=tm,
        )
        runner.run(x0, y0, T)
        ledger.close()
        back = RunLedger.events(d)
        # everything emitted landed, verbatim up to JSON normalization
        assert back == json.loads(
            json.dumps(tm.events, default=lambda o: o.tolist()
                       if hasattr(o, "tolist") else str(o))
        )
        assert sum(1 for e in back if e["name"] == "round") == T

    def test_manifest_records_seed_folds_and_digest(self, prob, tmp_path):
        sched = _flaky_schedule(5)
        d = str(tmp_path / "ledger")
        ledger = RunLedger(d)
        strategy = QuantizedGT(bits=8, seed=0)
        ledger.write_manifest(run_manifest(
            config={"rounds": T}, strategy=strategy,
            noise_seed=3, availability_seed=SEED, schedule=sched,
        ))
        man = RunLedger.manifest(d)
        assert man["config"] == {"rounds": T}
        assert man["strategy"]["class"] == "QuantizedGT"
        assert man["seeds"]["noise_stream"] == NOISE_STREAM
        assert man["seeds"]["availability_stream"] == AVAILABILITY_STREAM
        assert man["seeds"]["noise_seed"] == 3
        assert man["seeds"]["availability_seed"] == SEED
        import json

        from repro.obs.ledger import _jsonable

        digest = dict(sched.summary_trace())
        assert man["schedule"] == json.loads(
            json.dumps(digest, default=_jsonable)
        )

    def test_maybe_span_disabled_is_nullcontext(self):
        with maybe_span(None, "anything"):
            pass
        tm = Telemetry()
        with maybe_span(tm, "phase", dispatches=3):
            pass
        (ev,) = tm.series("span", "phase")
        assert ev["dispatches"] == 3 and ev["seconds"] >= 0.0


# -------------------------------------------------- metric_series contract
class TestMetricSeries:
    def test_empty_history_raises_with_available_keys(self, prob):
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, 2, ETA
        )
        with pytest.raises(ValueError, match=r"available metric keys: \[\]"):
            runner.metric_series("gap")

    def test_unknown_key_still_names_available(self, prob):
        runner = FederatedRunner.from_strategy(
            prob.loss, GradientTracking(), prob.agent_data, 2, ETA,
            metric_fn=lambda x, y: {"gap": jnp.sum(x * x)},
        )
        runner.run(x0, y0, 2)
        with pytest.raises(ValueError, match=r"\['gap'\]"):
            runner.metric_series("loss")
        assert runner.metric_series("gap").shape == (2,)


# ------------------------------------------------------------- peak memory
class TestPeakMemory:
    def test_benchmarks_shim_is_the_same_function(self):
        from benchmarks.common import peak_memory as shim

        assert shim is peak_memory

    def test_emits_counter_into_sink(self):
        tm = Telemetry()
        rec = peak_memory(
            lambda: np.zeros(100_000), telemetry=tm, label="alloc"
        )
        assert rec["host_peak_bytes"] > 0
        (ev,) = tm.series("counter", "peak_memory")
        assert ev["value"] == rec["host_peak_bytes"]
        assert ev["label"] == "alloc"
        assert ev["live_buffer_bytes"] == rec["live_buffer_bytes"]
