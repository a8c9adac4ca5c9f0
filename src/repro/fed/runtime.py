"""Synchronous federated round orchestration (single fused program).

`FederatedRunner` drives any round function produced by `repro.core` —
legacy constructors or the phase-split engine (`make_round`, the fused
composition of the `broadcast` / `exchange_corrections` / `local_steps` /
`aggregate` phases) with any `CommStrategy` — records per-round metrics
on the host, and periodically checkpoints; the single-host counterpart of
`repro.launch.train`.  Stateful strategies (client-sampling RNG,
error-feedback buffers) have their state initialized lazily on the first
round and threaded across rounds; build via `FederatedRunner.from_strategy`
for that path.  Stochastic strategies (a non-None `strategy.noise`) ride
the same state thread: `state["noise_key"]` is the dedicated noise
stream (`fed.noise.noise_key`), advanced once per round inside the
jitted round by `broadcast`, so checkpoint/resume replays the exact
noise sequence and the async runner — which samples the same stream
once server-side and slices per shard — consumes bit-identical draws.

This runner executes each round as ONE jitted program on the default
device: broadcast, exchange and K local steps lower together, so nothing
overlaps and strategy state is replicated.  Its asynchronous counterpart
— `repro.fed.async_runtime.AsyncFederatedRunner` — dispatches the same
phase functions per agent shard on separate devices, overlaps the
correction exchange with trailing local steps, and shards per-agent
strategy state; the two agree on iterates to fp tolerance
(tests/test_async_runtime.py).

Both runners also consume a `repro.sim.RoundSchedule` (`run(...,
schedule=...)`): per-round active sets and local-step budgets from a
seedable client population.  Non-full rounds execute the membership-
aware elastic round (`sim.make_elastic_round` — re-normalized weights,
tracker-table corrections, budget-gated local steps, EF re-anchoring
via the strategy's `rebase_state` hook; `rebase=False` is the
naive-server ablation).  A static-full schedule degenerates to the
unmodified legacy loop, so full participation stays bitwise identical
to running without a schedule (tests/test_elastic.py).

Both runners emit into an optional `repro.obs.Telemetry` sink
(`telemetry=...`): per-round spans, wire-byte counters
(`sim.per_agent_bytes` x the round's active count — the same
active-set-aware account `wire_report` prices), and sampled invariant
probes (`repro.obs.probes`).  `telemetry=None` (the default) runs the
pre-telemetry code verbatim — the sink lives entirely on the host, the
jitted round programs never change, and iterates stay bitwise identical
(tests/test_obs.py).  Per-phase device time comes from a profiler
trace of the fused program itself, whose phases carry their names
(`core.engine.make_phases`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..checkpoint import save_checkpoint

Pytree = Any


@dataclasses.dataclass
class RoundStats:
    round_index: int
    metrics: Dict[str, float]
    seconds: float


class RunnerHistoryMixin:
    """Per-round history, the wire report, telemetry emission and the
    elastic-schedule driver shared by the sync and async runners."""

    history: List[RoundStats]
    #: optional `repro.obs.Telemetry` sink — PUBLIC so tests can flip it
    #: on an already-compiled runner; None runs the pre-telemetry code
    #: verbatim (the bitwise pin, tests/test_obs.py)
    telemetry = None
    #: remembered by `run(..., schedule=...)` so `wire_report` defaults
    #: to the schedule the run actually executed
    _last_schedule = None
    _num_local_steps: Optional[int] = None
    _loss: Optional[Callable] = None

    def metric_series(self, name: str) -> np.ndarray:
        available = sorted({k for s in self.history for k in s.metrics})
        if name not in available:
            # also on an EMPTY history: a silent empty array for any
            # name hides typos exactly when a run produced nothing
            raise ValueError(
                f"unknown metric {name!r}; available metric keys: "
                f"{available}"
            )
        return np.array([s.metrics[name] for s in self.history])

    def wire_report(
        self,
        x: Pytree,
        y: Pytree,
        num_local_steps: int,
        schedule=None,
        pods=None,
    ) -> Dict:
        """Priced vs measured per-round communication for this runner's
        strategy: the analytic `bytes_per_round` next to the probe of the
        actual packed buffer lengths (`transport.measured_bytes_per_round`,
        headers included).  Requires a strategy-built runner.

        On an elastic/sparse run the full-participation price is wrong —
        only the schedule's active agents move bytes — so with a
        schedule (passed explicitly, or remembered from the last
        `run(..., schedule=...)`) the report adds the active-set-aware
        account via `sim.schedule_bytes`: the per-ACTIVE-agent payload
        (`sim.per_agent_bytes` — participation patched to 1, membership
        comes from the schedule) and the scheduled totals."""
        if self._strategy is None:
            raise ValueError("wire_report needs a runner built from_strategy")
        from .transport import measured_bytes_per_round

        report = {
            "bytes_per_round": int(
                self._strategy.bytes_per_round(x, y, num_local_steps)
            ),
            "measured_bytes_per_round": measured_bytes_per_round(
                self._strategy, x, y, num_local_steps
            ),
        }
        if schedule is None:
            schedule = self._last_schedule
        if schedule is not None and not getattr(
            schedule, "is_static_full", False
        ):
            from ..sim.elastic import per_agent_bytes, schedule_bytes

            totals = schedule_bytes(
                self._strategy, x, y, num_local_steps, schedule, pods=pods
            )
            report["scheduled_per_agent_bytes"] = per_agent_bytes(
                self._strategy, x, y, num_local_steps
            )
            report["scheduled_total_bytes"] = int(np.sum(totals))
            report["scheduled_mean_bytes_per_round"] = float(np.mean(totals))
        return report

    # ------------------------------------------------------- telemetry
    def _telemetry_state(self) -> Optional[Dict]:
        """The strategy-state dict probes read (EF residual buffers);
        overridden by runners that hold state elsewhere (sharded)."""
        return getattr(self, "_state", None)

    def _wire_counter_args(self, x, y, scheduled: bool = True
                           ) -> Optional[int]:
        """Per-agent payload for the "wire_bytes" counter; None when the
        runner lacks the strategy/K context (raw-round runners).  On a
        scheduled (elastic) run membership comes from the schedule, so
        the payload is `sim.per_agent_bytes` (participation patched to
        1) — the same account `wire_report` and `sim.schedule_bytes`
        price.  Unscheduled, the strategy's OWN client sampling governs
        and the payload is `measured_bytes_per_round` as-is."""
        if self._strategy is None or self._num_local_steps is None:
            return None
        if scheduled:
            from ..sim.elastic import per_agent_bytes

            return per_agent_bytes(
                self._strategy, x, y, self._num_local_steps
            )
        from .transport import measured_bytes_per_round

        return int(measured_bytes_per_round(
            self._strategy, x, y, self._num_local_steps
        ))

    def _emit_wire_probe(self, tm, x, y) -> None:
        """One-shot priced-vs-measured probe at run start."""
        if (
            self._strategy is None
            or self._num_local_steps is None
            or not tm.probe_due("priced_vs_measured", 0)
        ):
            return
        from ..obs import probes as _p

        tm.probe_value(
            "priced_vs_measured",
            0,
            _p.priced_vs_measured(
                self._strategy, x, y, self._num_local_steps
            ),
        )

    def _emit_probes(self, tm, t, x, y, tracker=None) -> None:
        """Sampled invariant probes shared by both runners — pure
        functions from `repro.obs.probes` over state the runner already
        holds.  `tracker` is the elastic tracker table on an elastic
        round; without one the GT residual recomputes the anchor
        corrections from the loss (full participation only)."""
        from ..obs import probes as _p

        if tm.probe_due("gt_residual", t):
            if tracker is not None:
                if tracker.get("gx") is not None:
                    cx, cy = _p.corrections_from_table(
                        tracker["gx"], tracker["gy"]
                    )
                    tm.probe_value("gt_residual", t, _p.gt_residual(cx, cy))
            elif (
                self._loss is not None
                and getattr(self._strategy, "use_correction", False)
                and getattr(self, "_agent_data", None) is not None
            ):
                from ..core.types import grad_xy

                cx, cy = _p.anchor_corrections(
                    grad_xy(self._loss), x, y, self._agent_data
                )
                tm.probe_value("gt_residual", t, _p.gt_residual(cx, cy))
        if tm.probe_due("ef_residual", t):
            norms = _p.ef_residual_norms(self._telemetry_state())
            if norms:
                tm.probe_value("ef_residual", t, norms)
        if tm.gap_fn is not None and tm.probe_due("duality_gap", t):
            tm.probe_value(
                "duality_gap", t, _p.duality_gap(tm.gap_fn, x, y)
            )

    def _drive_elastic(
        self,
        x,
        y,
        num_rounds: int,
        schedule,
        rebase: bool,
        log_every: int,
        elastic_state,
        init_tracker_fn: Callable,
        round_fn: Callable,
        label: str,
        checkpoint_fn: Optional[Callable] = None,
        num_agents: Optional[int] = None,
    ):
        """ONE owner of the elastic run loop for both runtimes:
        schedule validation, the `ElasticAggregator`, tracker +
        prev_active continuation (`elastic_state` — resuming without it
        re-anchors absent agents' trackers at the resume iterate and
        forgets who participated last round), per-round `n_active`
        metrics, history, logging and optional checkpointing.  The
        runners differ only in `round_fn(x, y, ev, agg, tracker,
        prev_active) -> (x, y, tracker)` — the fused elastic round vs
        per-shard dispatch."""
        import jax.numpy as jnp

        from ..sim.elastic import ElasticAggregator

        if len(schedule) < num_rounds:
            raise ValueError(
                f"schedule covers {len(schedule)} rounds, need {num_rounds}"
            )
        if num_agents is not None and schedule.m != num_agents:
            # a larger-m schedule would renormalize weights over agents
            # that don't exist and then silently lose their mass when
            # the runner slices — exactly the naive-server failure mode
            raise ValueError(
                f"schedule is for m={schedule.m} agents, runner has "
                f"{num_agents}"
            )
        agg = ElasticAggregator(self._strategy, rebase=rebase)
        if elastic_state is not None:
            tracker = elastic_state["tracker"]
            prev_active = elastic_state.get("prev_active")
        else:
            tracker = init_tracker_fn(x, y)
            prev_active = None
        tm = self.telemetry
        per_agent = None
        if tm is not None:
            self._emit_wire_probe(tm, x, y)
            per_agent = self._wire_counter_args(x, y)
        for t in range(num_rounds):
            t0 = time.perf_counter()
            ev = schedule[t]
            if tm is not None:
                tm.begin_round(t)
            x, y, tracker = round_fn(x, y, ev, agg, tracker, prev_active)
            prev_active = jnp.asarray(ev.active)
            metrics = {"n_active": float(ev.num_active)}
            if self._metric_fn is not None:
                metrics.update(
                    {k: float(v) for k, v in self._metric_fn(x, y).items()}
                )
            dt = time.perf_counter() - t0
            self.history.append(RoundStats(t, metrics, dt))
            if tm is not None:
                tm.round_event(
                    t, runtime=label, seconds=dt,
                    n_active=int(ev.num_active),
                )
                if per_agent is not None:
                    tm.counter(
                        "wire_bytes", per_agent * int(ev.num_active),
                        per_agent=per_agent, n_active=int(ev.num_active),
                    )
                self._emit_probes(tm, t, x, y, tracker=tracker)
                tm.end_round(t)
            if log_every and (t % log_every == 0 or t == num_rounds - 1):
                msg = " ".join(f"{k}={v:.3e}" for k, v in metrics.items())
                print(f"[{label} {t:5d}] {msg} ({dt*1e3:.1f} ms)")
            if checkpoint_fn is not None:
                checkpoint_fn(t, x, y, tracker, prev_active)
        #: where the run left off, for continuation:
        #: run(..., elastic_state=runner.elastic_state, schedule=tail)
        self.elastic_state = {"tracker": tracker, "prev_active": prev_active}
        return x, y


class FederatedRunner(RunnerHistoryMixin):
    def __init__(
        self,
        round_fn: Callable,
        agent_data: Pytree,
        metric_fn: Optional[Callable] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        strategy=None,
        elastic_round_fn: Optional[Callable] = None,
        tracker_init_fn: Optional[Callable] = None,
        telemetry=None,
    ):
        self._round = jax.jit(round_fn)
        self._agent_data = agent_data
        self._metric_fn = jax.jit(metric_fn) if metric_fn else None
        self._ckpt_dir = checkpoint_dir
        self._ckpt_every = checkpoint_every
        # non-None strategy with state => round_fn was built with
        # explicit_state=True and is called as round(x, y, data, state)
        self._strategy = strategy
        self._state: Optional[Pytree] = None
        #: repro.obs.Telemetry sink or None (None = pre-telemetry code
        #: verbatim); public so tests flip it on a compiled runner
        self.telemetry = telemetry
        # set by from_strategy — feed the wire counters / probes
        self._loss: Optional[Callable] = None
        self._num_local_steps: Optional[int] = None
        self._last_schedule = None
        # elastic (sim.RoundSchedule) support: the membership-aware round
        # round(x, y, data, state, tracker, weights, budgets, active)
        # and the tracker-table initializer (x, y, data) -> tracker.
        # Built by from_strategy; a raw-round runner cannot run elastic.
        self._elastic = (
            jax.jit(elastic_round_fn) if elastic_round_fn is not None else None
        )
        self._tracker_init = tracker_init_fn
        #: set by an elastic run: {"tracker", "prev_active"} where it
        #: left off (also checkpointed as "elastic_state")
        self.elastic_state: Optional[Dict] = None
        self.history: List[RoundStats] = []

    @classmethod
    def from_strategy(
        cls,
        loss: Callable,
        strategy,
        agent_data: Pytree,
        num_local_steps: int,
        eta_x: float,
        eta_y: Optional[float] = None,
        *,
        metric_fn: Optional[Callable] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        telemetry=None,
        **round_kwargs,
    ) -> "FederatedRunner":
        """Build the round for `strategy` (name or CommStrategy) via the
        unified engine and wrap it in a runner."""
        import functools

        from ..core.engine import make_round
        from ..sim.elastic import init_tracker, make_elastic_round
        from .strategies import resolve_strategy

        strategy = resolve_strategy(strategy)
        rnd = make_round(
            loss,
            strategy,
            num_local_steps,
            eta_x,
            eta_y,
            explicit_state=strategy.stateful,
            **round_kwargs,
        )
        elastic_kwargs = {
            k: v
            for k, v in round_kwargs.items()
            if k in ("proj_x", "proj_y", "update_fn", "constrain_agents")
        }
        elastic = make_elastic_round(
            loss, strategy, num_local_steps, eta_x, eta_y, **elastic_kwargs
        )
        runner = cls(
            rnd,
            agent_data,
            metric_fn=metric_fn,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            strategy=strategy,
            elastic_round_fn=elastic,
            tracker_init_fn=functools.partial(init_tracker, loss, strategy),
            telemetry=telemetry,
        )
        runner._loss = loss
        runner._num_local_steps = num_local_steps
        return runner

    @property
    def _stateful(self) -> bool:
        return self._strategy is not None and getattr(
            self._strategy, "stateful", False
        )

    def run(
        self,
        x: Pytree,
        y: Pytree,
        num_rounds: int,
        log_every: int = 0,
        state: Optional[Pytree] = None,
        schedule=None,
        rebase: bool = True,
        elastic_state: Optional[Dict] = None,
    ):
        if state is not None:  # resume from a checkpointed strategy_state
            self._state = state
        if self._stateful and self._state is None:
            m = jax.tree.leaves(self._agent_data)[0].shape[0]
            self._state = self._strategy.init_state(x, y, m)
        if schedule is not None and hasattr(schedule, "densify"):
            # a SparseRoundSchedule (O(active) id lists): this runner's
            # round math is m-dense, so densify — correct and bitwise
            # for simulation-scale m, but deliberately refused at a
            # scale where [T, m] masks defeat the sparse representation
            # (that regime belongs to sim.sparse.SparseElasticEngine)
            from ..sim.sparse import DENSE_FALLBACK_MAX_M

            if schedule.m > DENSE_FALLBACK_MAX_M:
                raise ValueError(
                    f"sparse schedule over m={schedule.m} agents is too "
                    f"large to densify (> {DENSE_FALLBACK_MAX_M}); use "
                    "sim.sparse.SparseElasticEngine for O(active) runs"
                )
            schedule = schedule.densify()
        if schedule is not None and schedule.is_static_full:
            # degenerate schedule (all agents, full budgets, every
            # round): the legacy loop below IS that run, bitwise
            schedule = None
        self._last_schedule = schedule
        if schedule is not None:
            return self._run_elastic(
                x, y, num_rounds, schedule, rebase, log_every,
                elastic_state,
            )
        tm = self.telemetry
        per_agent = None
        if tm is not None:
            self._emit_wire_probe(tm, x, y)
            per_agent = self._wire_counter_args(x, y, scheduled=False)
        for t in range(num_rounds):
            t0 = time.perf_counter()
            if tm is not None:
                tm.begin_round(t)
            if self._stateful:
                x, y, self._state = self._round(
                    x, y, self._agent_data, self._state
                )
            else:
                x, y = self._round(x, y, self._agent_data)
            metrics = {}
            if self._metric_fn is not None:
                metrics = {
                    k: float(v)
                    for k, v in self._metric_fn(x, y).items()
                }
            dt = time.perf_counter() - t0
            self.history.append(RoundStats(t, metrics, dt))
            if tm is not None:
                tm.round_event(t, runtime="sync", seconds=dt)
                if per_agent is not None:
                    m = jax.tree.leaves(self._agent_data)[0].shape[0]
                    tm.counter(
                        "wire_bytes", per_agent * m,
                        per_agent=per_agent, n_active=m,
                    )
                self._emit_probes(tm, t, x, y)
                tm.end_round(t)
            if log_every and (t % log_every == 0 or t == num_rounds - 1):
                msg = " ".join(f"{k}={v:.3e}" for k, v in metrics.items())
                print(f"[round {t:5d}] {msg} ({dt*1e3:.1f} ms)")
            if (
                self._ckpt_dir
                and self._ckpt_every
                and (t + 1) % self._ckpt_every == 0
            ):
                payload = {"x": x, "y": y}
                if self._state is not None:
                    # resuming without this replays RNG draws / zeroes the
                    # error-feedback buffers
                    payload["strategy_state"] = self._state
                save_checkpoint(self._ckpt_dir, t + 1, payload)
        return x, y

    def _run_elastic(
        self, x, y, num_rounds, schedule, rebase, log_every,
        elastic_state=None,
    ):
        """Drive `num_rounds` through the membership-aware elastic round
        (see `repro.sim.elastic`): per-round weights re-normalized over
        the schedule's active set, local steps capped by its budgets,
        the tracker table threaded across rounds, and the strategy's
        membership-dependent state re-anchored via `rebase_state`.
        `rebase=False` is the naive-server ablation (1/m weights, stale
        EF residuals).

        Checkpoints made on this path carry an `elastic_state` entry
        ({"tracker": ..., "prev_active": ...}) alongside the strategy
        state: resuming WITHOUT it would re-anchor every absent agent's
        tracker at the resume iterate and forget who participated last
        round, silently diverging from the uninterrupted run.  Resume
        with `run(..., state=ckpt["strategy_state"],
        elastic_state=ckpt["elastic_state"],
        schedule=schedule.tail(t_ckpt))`."""
        import jax.numpy as jnp

        if self._elastic is None or self._strategy is None:
            raise ValueError(
                "elastic schedules need a runner built via from_strategy"
            )
        state = self._state if self._state is not None else {}

        def round_fn(x, y, ev, agg, tracker, prev_active):
            nonlocal state
            active = jnp.asarray(ev.active)
            weights = agg.weights(active)
            budgets = jnp.asarray(ev.budgets)
            # EF re-anchoring happens INSIDE the jitted round (fused
            # with the state's first use); None = the naive ablation
            x, y, state, tracker = self._elastic(
                x, y, self._agent_data, state, tracker,
                weights, budgets, active,
                agg.round_prev_active(active, prev_active),
            )
            if self._stateful:
                # keep the probe-visible state current mid-run (the
                # mixin's ef_residual probe reads `_telemetry_state`)
                self._state = state
            return x, y, tracker

        def checkpoint_fn(t, x, y, tracker, prev_active):
            if not (
                self._ckpt_dir
                and self._ckpt_every
                and (t + 1) % self._ckpt_every == 0
            ):
                return
            payload = {
                "x": x,
                "y": y,
                # resuming without this re-anchors absent agents'
                # trackers at the resume iterate and forgets the
                # previous active set (see docstring)
                "elastic_state": {
                    "tracker": tracker,
                    "prev_active": prev_active,
                },
            }
            if self._stateful:
                payload["strategy_state"] = state
            save_checkpoint(self._ckpt_dir, t + 1, payload)

        x, y = self._drive_elastic(
            x, y, num_rounds, schedule, rebase, log_every, elastic_state,
            lambda xx, yy: self._tracker_init(xx, yy, self._agent_data),
            round_fn, "elastic round", checkpoint_fn,
            num_agents=jax.tree.leaves(self._agent_data)[0].shape[0],
        )
        if self._stateful:
            self._state = state
        return x, y
