"""Asynchronous multi-device federated round driver.

`AsyncFederatedRunner` executes the SAME round phases as the fused
single-program engine (`repro.core.engine.make_phases`) but dispatches
them per **agent shard** on separate devices, so the schedule — not the
math — changes:

  * the m agents are split into contiguous shards, one per device; each
    shard's `agent_data` slice and per-agent strategy state (error-
    feedback buffers — `strategy.sharded_state_keys`) live on that
    shard's device permanently, instead of replicating the full stack;
  * `broadcast` + the anchor-gradient half of `exchange_corrections`
    run per shard as independently dispatched programs (one XLA stream
    per device — jax's async dispatch keeps every shard's queue busy
    while the host runs ahead);
  * the server half of the exchange — participation sampling, gbar,
    forming c_i = gbar - g_i, the strategy's `transform_correction`
    (identical code, identical RNG draws as the sync path, so iterates
    match `FederatedRunner` to fp tolerance) and the packed-payload
    decode — runs on the server device over the gathered gradients.
    A strategy whose transform is the identity
    (`transforms_correction` false: plain and partial-participation GT)
    needs none of that server work per agent: the shards' weighted
    partial sums of their gradients are added up shard to shard into
    gbar, which is broadcast, and each shard forms its own c_i — so
    the server device holds gbar instead of two model copies per
    agent;
  * `local_steps` runs per shard with its correction slice; the shard
    returns a weighted PARTIAL aggregate (`core.agent_weighted_sum`);
    the partials are added up shard to shard (`_chain_sum`) and the
    server projects the total;
  * the next round's `broadcast` transfer is **double-buffered**: the
    server enqueues `jax.device_put` of (x^{t+1}, y^{t+1}) to every
    shard device as soon as the aggregate is dispatched (before its
    values are ready), while the previous round's broadcast buffers are
    still feeding trailing local steps — and those consumed buffers are
    donated into the local-step program, so the transfer of round t+1
    overlaps the tail of round t instead of serializing behind it.

FullSync (sync_every_step) has no local divergence to overlap: the
runner executes its K communicated steps as K (per-shard grad-sum →
server combine) exchanges per round — which is exactly why it is K times
more expensive on the wire, now visible as wall-clock in
`benchmarks/comm_efficiency.py --overlap`.

The runner also consumes an elastic `repro.sim.RoundSchedule`
(`run(..., schedule=...)`): agent SHARDS join and leave between rounds —
a shard whose agents are all absent this round is never dispatched (its
anchor-gradient and local-step programs simply don't run; stale tracker
rows stand in server-side), and partially-present shards run
budget-gated local steps with their weight slice re-normalized over the
global active set.  Membership is identical to the sync runner's by
construction (both read the same materialized schedule), and the
tracker-table exchange runs server-side through the same
`sim.make_elastic_round` math, so elastic iterates match the sync
elastic path to fp tolerance.  The elastic rounds forgo the
double-buffered donated broadcasts (membership changes the set of live
shard programs round to round); a static-full schedule falls back to
the unmodified overlapped loop.

The fp-tolerance contract with the sync runner holds because per-agent
gradients and local steps are elementwise identical computations on
shard slices, and every random draw (participation sampling, rand-k
selection scores, stochastic-rounding uniforms, and the per-agent
gradient-noise keys of a stochastic strategy — `_round_noise_keys`,
sliced per shard exactly like the participation weights) happens once,
server-side, through the very same `strategy` code path; only the
aggregate's reduction order differs (per-shard sums combined
server-side vs one mean), which is the usual ~ulp-level float
non-associativity.  Stochastic strategies' noise keys are folded by
GLOBAL agent index (`fed.noise`), so a shard's draws do not depend on
how agents were split into shards.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..core.engine import (
    agent_mean,
    agent_weighted_sum,
    make_noise_vgrad,
    make_phases,
    noise_eval_keys,
    tracking_corrections,
)
from ..core.types import Pytree, grad_xy, identity_proj
from .runtime import RoundStats, RunnerHistoryMixin
from .strategies import resolve_strategy


def _num_agents(agent_data: Pytree) -> int:
    return jax.tree.leaves(agent_data)[0].shape[0]


def _slice_agents(tree: Pytree, lo: int, hi: int) -> Pytree:
    return jax.tree.map(lambda u: u[lo:hi], tree)


def largest_shard_count(m: int, n_devices: int) -> int:
    """Most shards we can use: the largest divisor of m that fits the
    device count (contiguous equal shards keep every program shape
    static and identical across shards — one compilation serves all).
    Shared with `launch.multihost`."""
    for n in range(min(m, n_devices), 0, -1):
        if m % n == 0:
            return n
    return 1


def concat_on_device(parts: List[Pytree], device) -> Pytree:
    """Gather per-shard pytrees onto one device and re-stack the agent
    axis (the up-link transfer of a sharded schedule).  Shared with
    `launch.multihost`."""
    parts = [jax.device_put(p, device) for p in parts]
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *u: jnp.concatenate(u, axis=0), *parts)


class AsyncFederatedRunner(RunnerHistoryMixin):
    """Drive federated rounds with per-agent-shard phase programs on
    separate devices (see module docstring).

    Mirrors `FederatedRunner`'s surface: `run(x, y, num_rounds)` returns
    the final iterates, `history` / `metric_series` record per-round
    metrics, `wire_report` prices the strategy.  Construction takes the
    loss + strategy directly (there is no externally-built round
    function to wrap — the runner owns the phase schedule)."""

    def __init__(
        self,
        loss: Callable,
        strategy,
        agent_data: Pytree,
        num_local_steps: int,
        eta_x: float,
        eta_y: Optional[float] = None,
        *,
        proj_x: Callable = identity_proj,
        proj_y: Callable = identity_proj,
        metric_fn: Optional[Callable] = None,
        devices: Optional[Sequence] = None,
        pod_map=None,
        telemetry=None,
        **strategy_kwargs,
    ):
        self._strategy = resolve_strategy(strategy, **strategy_kwargs)
        self._K = num_local_steps
        #: repro.obs.Telemetry sink or None (None = pre-telemetry code
        #: verbatim); public so tests flip it on a compiled runner
        self.telemetry = telemetry
        self._loss = loss
        self._num_local_steps = num_local_steps
        self._eta_x = eta_x
        self._eta_y = eta_x if eta_y is None else eta_y
        self._proj_x = proj_x
        self._proj_y = proj_y
        self._m = _num_agents(agent_data)

        devices = list(devices) if devices is not None else jax.local_devices()
        self._pod_map = pod_map
        if pod_map is not None:
            # pod-aligned sharding: pick a shard count dividing the pod
            # count so every shard holds whole pods — the existing
            # skip-absent-shards dispatch below then doubles as "skip
            # quiet pods" with no pod-specific branching (fed.pods)
            from .pods import pod_aligned_shard_count

            if pod_map.m != self._m:
                raise ValueError(
                    f"pod_map is for m={pod_map.m}, runner has {self._m}"
                )
            if self._m % pod_map.num_pods != 0:
                raise ValueError(
                    f"pod-aligned sharding needs m divisible by the pod "
                    f"count, got m={self._m}, pods={pod_map.num_pods}"
                )
            self._n_shards = pod_aligned_shard_count(
                pod_map.num_pods, len(devices)
            )
        else:
            self._n_shards = largest_shard_count(self._m, len(devices))
        self._per = self._m // self._n_shards
        #: server device: owns the exchange transform, sampling RNG and
        #: the aggregate; also hosts shard 0 (a dedicated server device
        #: would idle during local steps on small hosts)
        self._server = devices[0]
        self._shard_devices = devices[: self._n_shards]
        self._data_s = [
            jax.device_put(
                _slice_agents(agent_data, i * self._per, (i + 1) * self._per),
                d,
            )
            for i, d in enumerate(self._shard_devices)
        ]

        self._phases = make_phases(
            loss,
            self._strategy,
            num_local_steps,
            eta_x,
            eta_y,
            proj_x=proj_x,
            proj_y=proj_y,
        )
        self._gfn = grad_xy(loss)
        self._vgrad = jax.vmap(self._gfn, in_axes=(0, 0, 0))
        self._use_corr = bool(getattr(self._strategy, "use_correction", False))
        #: corrections formed shard-side from the broadcast gbar (see
        #: the module docstring); the server path otherwise
        self._shard_corr = self._use_corr and not getattr(
            self._strategy, "transforms_correction", True
        )
        self._sync_every = bool(
            getattr(self._strategy, "sync_every_step", False)
        )
        self._cdt = getattr(self._strategy, "correction_dtype", None)
        self._noise = getattr(self._strategy, "noise", None)
        self._nvgrad = (
            make_noise_vgrad(self._gfn, self._noise)
            if self._noise is not None
            else None
        )
        self._fused = (
            self._use_corr
            and self._m > 1
            and bool(self._strategy.exact_correction)
            # momentum folds the correction into a velocity, so the
            # first step is no longer the plain anchor update
            and not getattr(self._strategy, "momentum", 0.0)
        )
        self._build_programs()

        self._metric_fn = jax.jit(metric_fn) if metric_fn else None
        self._server_state: Dict = {}
        self._shard_state: Optional[List[Dict]] = None
        #: set by an elastic run: {"tracker", "prev_active"} where it
        #: left off (mirrors FederatedRunner.elastic_state)
        self.elastic_state: Optional[Dict] = None
        self.history: List[RoundStats] = []

    @property
    def pods_per_shard(self) -> Optional[int]:
        """Whole pods per agent shard under pod-aligned sharding (None
        without a pod_map) — a quiet run of this many consecutive pods
        makes its shard's programs skip entirely."""
        if self._pod_map is None:
            return None
        return self._pod_map.num_pods // self._n_shards

    # ------------------------------------------------------------ programs
    def _build_programs(self) -> None:
        ph = self._phases
        strategy = self._strategy
        cdt = self._cdt
        fused = self._fused

        noise = self._noise

        def shard_grads(x, y, data_s, nk_s=None):
            """Per-shard anchor gradients (the up half of the exchange).
            `nk_s` is this shard's slice of the round's per-agent noise
            keys; None — a noiseless strategy, or the tracker init,
            which must match the sync path's deterministic
            `sim.init_tracker` — is the exact oracle (the dispatch is
            trace-time: None vs array is part of the jit signature)."""
            rs = ph.broadcast(x, y, data_s, {}, weights=None,
                              noise_keys=nk_s)
            if noise is None or nk_s is None:
                g = self._vgrad(rs.xs, rs.ys, data_s)
            else:
                g = self._nvgrad(
                    noise_eval_keys(rs.noise_keys, 0), rs.xs, rs.ys, data_s
                )
            return g.gx, g.gy

        def shard_grad_sums(x, y, data_s, w_s, nk_s=None):
            """Anchor gradients plus their weighted partial sum — the
            shard's share of gbar (the shard keeps the gradients)."""
            gx, gy = shard_grads(x, y, data_s, nk_s)
            return (gx, gy, agent_weighted_sum(gx, w_s),
                    agent_weighted_sum(gy, w_s))

        def shard_corrections(gx, gy, gbar_x, gbar_y):
            """The shard's own c_i = gbar - g_i (identity transform)."""
            return tracking_corrections(gx, gy, gbar_x, gbar_y, cdt)

        def shard_point_grads(x, y, data_s):
            """Per-agent gradients at the SHARED point (FullSync: every
            'local' step is evaluated at the current global iterate)."""
            g = jax.vmap(self._gfn, in_axes=(None, None, 0))(x, y, data_s)
            return g.gx, g.gy

        def fullsync_step(x, y, gx, gy, weights):
            """One centralized GDA step from gathered per-agent grads;
            weights None is the bitwise-pinned uniform mean, an elastic
            round passes its re-normalized active-set weights."""
            gxm = agent_mean(gx, weights)
            gym = agent_mean(gy, weights)
            x1 = self._proj_x(
                jax.tree.map(lambda u, v: u - self._eta_x * v, x, gxm)
            )
            y1 = self._proj_y(
                jax.tree.map(lambda u, v: u + self._eta_y * v, y, gym)
            )
            return x1, y1

        def server_exchange(gx, gy, state, weights):
            """Server half of exchange_corrections: gbar, corrections,
            strategy transform (same draws as the sync path), decode."""
            gbar_x = agent_mean(gx, weights)
            gbar_y = agent_mean(gy, weights)
            cx, cy = tracking_corrections(gx, gy, gbar_x, gbar_y, cdt)
            cx, cy, state = strategy.transform_correction(cx, cy, state)
            if hasattr(cx, "decode"):
                cx = cx.decode()
            if hasattr(cy, "decode"):
                cy = cy.decode()
            return cx, cy, gbar_x, gbar_y, state

        def shard_steps(x, y, data_s, cx_s, cy_s, gbar_x, gbar_y, w_s,
                        b_s=None, nk_s=None):
            """Per-shard local_steps + partial aggregate — ONE body for
            both schedules (b_s None is the legacy pinned trace; an
            elastic round passes its budget slice).  It is jitted twice
            below: the legacy instance DONATES the broadcast buffers
            (x, y) — by the time it runs they have served the gradient
            program, and freeing them lets the next round's
            double-buffered transfer land without growing the working
            set — while the elastic instance re-broadcasts per round
            (the set of live shard programs changes with membership, so
            there is no stable double-buffer to donate into)."""
            rs = ph.broadcast(x, y, data_s, {}, weights=None,
                              step_budgets=b_s, noise_keys=nk_s)
            rs = dataclasses.replace(
                rs, cx=cx_s, cy=cy_s, gbar_x=gbar_x, gbar_y=gbar_y,
                fused=fused,
            )
            rs = ph.local_steps(rs, data_s)
            return (
                agent_weighted_sum(rs.xs, w_s),
                agent_weighted_sum(rs.ys, w_s),
            )

        def add(total, part):
            return jax.tree.map(jnp.add, total, part)

        def project(x, y):
            return self._proj_x(x), self._proj_y(y)

        def zeros_like_agents(bx, by):
            """m == 1: the correction is identically zero and elided."""
            z = lambda t: jax.tree.map(
                lambda u: jnp.zeros((1,) + u.shape, u.dtype), t
            )
            return z(bx), z(by)

        def server_exchange_elastic(gx, gy, state, active, tab_x, tab_y,
                                    prev_active):
            """Membership-aware server exchange: one thin jit wrapper
            over `sim.elastic.tracker_exchange` — the SAME function the
            sync elastic round fuses, so the GT-invariant math (and the
            in-jit EF re-anchoring) has one owner whatever the
            execution schedule (skipped shards deliver zero-filled
            gradient rows that the active mask discards in favor of the
            stale tracker rows)."""
            from ..sim.elastic import tracker_exchange

            return tracker_exchange(
                strategy, gx, gy, state, active, tab_x, tab_y, cdt,
                prev_active,
            )

        self._shard_grads = jax.jit(shard_grads)
        self._shard_grad_sums = jax.jit(shard_grad_sums)
        # the gradients are dead once the corrections exist: donate them
        self._shard_corrections = jax.jit(
            shard_corrections, donate_argnums=(0, 1)
        )
        # the running total arrives as a fresh transfer: donate it
        self._add = jax.jit(add, donate_argnums=(0,))
        self._project = jax.jit(project)
        self._shard_point_grads = jax.jit(shard_point_grads)
        self._fullsync_step = jax.jit(fullsync_step)
        self._server_exchange = jax.jit(server_exchange)
        self._shard_steps = jax.jit(shard_steps, donate_argnums=(0, 1))
        self._zeros_like_agents = jax.jit(zeros_like_agents)
        self._server_exchange_elastic = jax.jit(server_exchange_elastic)
        self._shard_steps_elastic = jax.jit(shard_steps)

    # ---------------------------------------------------------- state plumbing
    def _init_state(self, x: Pytree, y: Pytree) -> None:
        strategy = self._strategy
        if not getattr(strategy, "stateful", False):
            self._server_state = {}
            self._shard_state = [{} for _ in range(self._n_shards)]
            return
        full = strategy.init_state(x, y, self._m)
        sharded_keys = tuple(
            k for k in getattr(strategy, "sharded_state_keys", ()) if k in full
        )
        self._server_state = {
            k: jax.device_put(v, self._server)
            for k, v in full.items()
            if k not in sharded_keys
        }
        per = self._per
        self._shard_state = [
            {
                k: jax.device_put(
                    _slice_agents(full[k], i * per, (i + 1) * per), d
                )
                for k in sharded_keys
            }
            for i, d in enumerate(self._shard_devices)
        ]
        self._sharded_keys = sharded_keys

    def _gather_state(self) -> Dict:
        """Full strategy state on the server device: sharded entries are
        gathered (they ride the same up-link as the corrections they
        compensate), the rest already live there."""
        state = dict(self._server_state)
        for k in getattr(self, "_sharded_keys", ()):
            parts = [
                jax.device_put(s[k], self._server) for s in self._shard_state
            ]
            state[k] = jax.tree.map(
                lambda *u: jnp.concatenate(u, axis=0), *parts
            )
        return state

    def _scatter_state(self, state: Dict) -> None:
        """Split the transform's updated state back: per-agent entries to
        their shard devices, the rest stays server-side."""
        per = self._per
        for k in getattr(self, "_sharded_keys", ()):
            full = state.pop(k)
            for i, (s, d) in enumerate(
                zip(self._shard_state, self._shard_devices)
            ):
                s[k] = jax.device_put(
                    _slice_agents(full, i * per, (i + 1) * per), d
                )
        self._server_state = state

    # ------------------------------------------------------------- round loop
    def _round_weights(self):
        """Participation sampling, once per round, server-side — shards
        receive their weight slices instead of re-sampling (the draws
        must match the sync path's exactly)."""
        strategy = self._strategy
        state = self._server_state
        weights, state = strategy.sample_weights(state, self._m)
        self._server_state = state
        if weights is None:
            w = jnp.full((self._m,), 1.0 / self._m)
        else:
            w = weights
        per = self._per
        w_slices = [
            jax.device_put(w[i * per : (i + 1) * per], d)
            for i, d in enumerate(self._shard_devices)
        ]
        return weights, w_slices

    def _round_noise_keys(self):
        """Per-agent gradient-noise keys, once per round, server-side —
        shards receive their slices (mirrors `_round_weights`: the draws
        must match the sync path's exactly, which holds because the keys
        are folded by global agent index — see `fed.noise`)."""
        if self._noise is None:
            return [None] * self._n_shards
        keys, state = self._strategy.sample_noise_keys(
            self._server_state, self._m
        )
        self._server_state = state
        per = self._per
        return [
            jax.device_put(keys[i * per : (i + 1) * per], d)
            for i, d in enumerate(self._shard_devices)
        ]

    def _run_fullsync_round(self, x, y, weights=None, shard_live=None):
        """FullSync: K communicated steps; each is a per-shard gradient
        fan-out + server combine (no local divergence to overlap).
        `weights` None is the legacy uniform mean; an elastic round
        passes its re-normalized active-set weights (budgets are
        meaningless here — there are no local phases to cap) and
        `shard_live`, so fully-absent shards are never dispatched —
        their zero-weight rows are zero-filled server-side."""
        zx = zy = None
        if shard_live is not None and not all(shard_live):
            zx, zy = self._zero_shard_rows(x, y)
        for _ in range(self._K):
            gs = [
                self._shard_point_grads(
                    jax.device_put(x, d), jax.device_put(y, d), data
                )
                if shard_live is None or shard_live[i]
                else None
                for i, (d, data) in enumerate(
                    zip(self._shard_devices, self._data_s)
                )
            ]
            gx = self._concat_server(
                [g[0] if g is not None else zx for g in gs]
            )
            gy = self._concat_server(
                [g[1] if g is not None else zy for g in gs]
            )
            x, y = self._fullsync_step(x, y, gx, gy, weights)
        return x, y

    def _bcast(self, x, y) -> List:
        """Double-buffer fill: fresh per-shard (x, y) broadcast buffers.
        Cross-device `device_put` transfers into a new buffer; for the
        shard sharing the server device the copy is explicit —
        `device_put` to the resident device is a no-op alias, and these
        buffers are DONATED into the local-step program, which must
        never delete an array the caller (or the next round) still
        owns."""
        out = []
        for d in self._shard_devices:
            if d == self._server:
                out.append(
                    (jax.tree.map(jnp.copy, x), jax.tree.map(jnp.copy, y))
                )
            else:
                out.append((jax.device_put(x, d), jax.device_put(y, d)))
        return out

    def _chain_sum(self, parts: List[Pytree], devices=None) -> Pytree:
        """Sum per-shard partial sums (they already carry the
        participation weights, or 1/m) and land the total on the server.
        The running total hops from shard to shard in order, so each
        device receives one addend and the server only the total — not
        one copy per shard — and the order ((p0 + p1) + p2) + ... is the
        one the single-program sum uses."""
        devices = self._shard_devices if devices is None else devices
        total = parts[0]
        for part, d in zip(parts[1:], devices[1:]):
            total = self._add(jax.device_put(total, d), part)
        return jax.device_put(total, self._server)

    def _concat_server(self, parts: List[Pytree]) -> Pytree:
        return concat_on_device(parts, self._server)

    def _zero_shard_rows(self, x, y):
        """One shard's worth of zero-filled per-agent gradient rows —
        the stand-in for a shard that was never dispatched this round
        (the active mask / zero weights discard them downstream).  ONE
        owner of the placeholder layout for both elastic paths."""
        z = lambda t: jax.tree.map(
            lambda u: jnp.zeros((self._per,) + u.shape, u.dtype), t
        )
        return z(x), z(y)

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
        x = jax.device_put(x, self._server)
        y = jax.device_put(y, self._server)
        if self._shard_state is None:
            self._init_state(x, y)
            if state is not None:
                # resume: re-split a checkpointed full state
                self._scatter_state(dict(state))
        if schedule is not None and schedule.is_static_full:
            # degenerate schedule: the overlapped legacy loop below IS
            # the full-participation run
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
        # double-buffered broadcast: the per-shard (x, y) copies for the
        # round ABOUT to run; refreshed (device_put enqueued) as soon as
        # the aggregate producing the next iterates is dispatched.
        # FullSync has no local phase to pre-feed — its per-step fan-out
        # transfers live inside _run_fullsync_round
        bcast = None if self._sync_every else self._bcast(x, y)
        for t in range(num_rounds):
            t0 = time.perf_counter()
            if tm is not None:
                tm.begin_round(t)
            if self._sync_every:
                x, y = self._run_fullsync_round(x, y)
            else:
                x, y, bcast = self._run_round(x, y, bcast)
            metrics = {}
            if self._metric_fn is not None:
                metrics = {
                    k: float(v) for k, v in self._metric_fn(x, y).items()
                }
            dt = time.perf_counter() - t0
            self.history.append(RoundStats(t, metrics, dt))
            if tm is not None:
                tm.round_event(
                    t, runtime="async", seconds=dt,
                    n_shards=self._n_shards,
                )
                if per_agent is not None:
                    tm.counter(
                        "wire_bytes", per_agent * self._m,
                        per_agent=per_agent, n_active=self._m,
                    )
                self._emit_probes(tm, t, x, y)
                tm.end_round(t)
            if log_every and (t % log_every == 0 or t == num_rounds - 1):
                msg = " ".join(f"{k}={v:.3e}" for k, v in metrics.items())
                print(f"[async round {t:5d}] {msg} ({dt*1e3:.1f} ms)")
        jax.block_until_ready((x, y))
        return x, y

    def _run_round(self, x, y, bcast):
        from ..obs.telemetry import maybe_span

        tm = self.telemetry
        weights, w_slices = self._round_weights()
        nk_slices = self._round_noise_keys()
        per = self._per
        cx_s = cy_s = [None] * self._n_shards
        gbx_s = gby_s = [None] * self._n_shards
        if self._shard_corr and self._m > 1:
            with maybe_span(tm, "exchange_corrections",
                            dispatches=self._n_shards):
                gs = [
                    self._shard_grad_sums(bx, by, data, wi, nk)
                    for (bx, by), data, wi, nk in zip(
                        bcast, self._data_s, w_slices, nk_slices
                    )
                ]
                gbar_x = self._chain_sum([g[2] for g in gs])
                gbar_y = self._chain_sum([g[3] for g in gs])
                gbx_s = [
                    jax.device_put(gbar_x, d) for d in self._shard_devices
                ]
                gby_s = [
                    jax.device_put(gbar_y, d) for d in self._shard_devices
                ]
                cs = [
                    self._shard_corrections(g[0], g[1], gbx, gby)
                    for g, gbx, gby in zip(gs, gbx_s, gby_s)
                ]
                del gs
                cx_s = [c[0] for c in cs]
                cy_s = [c[1] for c in cs]
        elif self._use_corr and self._m > 1:
            # fan-out: every shard's anchor-gradient program is dispatched
            # before any result is awaited (async dispatch == one stream
            # per device); the device_put gathers below overlap shards
            # that are still computing
            with maybe_span(tm, "exchange_corrections",
                            dispatches=self._n_shards):
                gs = [
                    self._shard_grads(bx, by, data, nk)
                    for (bx, by), data, nk in zip(
                        bcast, self._data_s, nk_slices
                    )
                ]
                gx = self._concat_server([g[0] for g in gs])
                gy = self._concat_server([g[1] for g in gs])
                full_state = self._gather_state()
                cx, cy, gbar_x, gbar_y, new_state = self._server_exchange(
                    gx, gy, full_state, weights
                )
                self._scatter_state(dict(new_state))
                # down-link: correction slices + the global anchor gradient
                cx_s = [
                    jax.device_put(
                        _slice_agents(cx, i * per, (i + 1) * per), d
                    )
                    for i, d in enumerate(self._shard_devices)
                ]
                cy_s = [
                    jax.device_put(
                        _slice_agents(cy, i * per, (i + 1) * per), d
                    )
                    for i, d in enumerate(self._shard_devices)
                ]
                gbx_s = [
                    jax.device_put(gbar_x, d) for d in self._shard_devices
                ]
                gby_s = [
                    jax.device_put(gbar_y, d) for d in self._shard_devices
                ]
        elif self._use_corr:
            # m == 1: correction identically zero — build it shard-side
            z = [self._zeros_like_agents(bx, by) for (bx, by) in bcast]
            cx_s = [zi[0] for zi in z]
            cy_s = [zi[1] for zi in z]

        with maybe_span(tm, "local_steps", dispatches=self._n_shards):
            sums = [
                self._shard_steps(
                    bx, by, data, cxi, cyi, gbxi, gbyi, wi, None, nki
                )
                for (bx, by), data, cxi, cyi, gbxi, gbyi, wi, nki in zip(
                    bcast, self._data_s, cx_s, cy_s, gbx_s, gby_s, w_slices,
                    nk_slices,
                )
            ]
        with maybe_span(tm, "aggregate"):
            x1, y1 = self._project(
                self._chain_sum([a for a, _ in sums]),
                self._chain_sum([b for _, b in sums]),
            )
        # double-buffer flip: enqueue next round's broadcast immediately
        # (the transfers ride behind the still-executing local steps; the
        # donated buffers they replace free as those programs retire)
        with maybe_span(tm, "broadcast", dispatches=self._n_shards):
            bcast = self._bcast(x1, y1)
        return x1, y1, bcast

    # ---------------------------------------------------------- elastic rounds
    def _run_elastic(self, x, y, num_rounds, schedule, rebase, log_every,
                     elastic_state=None):
        """Drive `num_rounds` through the membership-aware schedule:
        shards join/leave between rounds (fully-absent shards are never
        dispatched), budgets gate local steps, the tracker table lives
        server-side.  Same schedule + same strategy draws as the sync
        runner's elastic loop => iterates match to fp tolerance.

        The loop itself is the shared `RunnerHistoryMixin._drive_elastic`
        driver, so validation, continuation (`elastic_state` +
        `schedule.tail`) and per-round bookkeeping cannot drift between
        the runtimes; only the per-round step differs (per-shard
        dispatch here, the fused elastic program in `FederatedRunner`).
        The tracker table initializes lazily from the first round's
        broadcast (the per-shard agent data never leaves its device)."""
        x, y = self._drive_elastic(
            x, y, num_rounds, schedule, rebase, log_every, elastic_state,
            lambda xx, yy: None,  # lazy: built from the first broadcast
            self._run_elastic_round, "elastic async round",
            num_agents=self._m,
        )
        jax.block_until_ready((x, y))
        return x, y

    def _init_tracker(self, bcast):
        """Tracker table at the first elastic round: every agent's
        anchor gradient at the current broadcast iterate, gathered from
        ALL shards once (matches `sim.init_tracker` on the sync path)."""
        gs = [
            self._shard_grads(bx, by, data)
            for (bx, by), data in zip(bcast, self._data_s)
        ]
        return {
            "gx": self._concat_server([g[0] for g in gs]),
            "gy": self._concat_server([g[1] for g in gs]),
        }

    def _run_elastic_round(self, x, y, ev, agg, tracker, prev_active):
        from ..obs.telemetry import maybe_span

        tm = self.telemetry
        per = self._per
        active = jax.device_put(jnp.asarray(ev.active), self._server)
        weights = agg.weights(active)
        n = self._n_shards
        shard_live = [
            bool(ev.active[i * per : (i + 1) * per].any()) for i in range(n)
        ]
        if tm is not None:
            for i, live in enumerate(shard_live):
                if not live:
                    tm.emit("event", "shard_skipped", shard=i)

        if self._sync_every:
            x, y = self._run_fullsync_round(x, y, weights, shard_live)
            return x, y, tracker

        budgets = jnp.asarray(ev.budgets)
        # one noise draw per round, server-side, exactly as the sync
        # elastic round's broadcast samples it — including for absent
        # agents, whose keys are drawn and discarded (the fold tree is
        # positional, so presence cannot shift other agents' draws)
        nk_slices = self._round_noise_keys()
        # fresh per-shard broadcast (no donation — see shard_steps_elastic);
        # absent shards still receive it cheaply enough, keeping the
        # transfer schedule uniform
        with maybe_span(tm, "broadcast", dispatches=n):
            bcast = [
                (jax.device_put(x, d), jax.device_put(y, d))
                for d in self._shard_devices
            ]
        w_slices = [
            jax.device_put(weights[i * per : (i + 1) * per], d)
            for i, d in enumerate(self._shard_devices)
        ]
        b_slices = [
            jax.device_put(budgets[i * per : (i + 1) * per], d)
            for i, d in enumerate(self._shard_devices)
        ]

        cx_s = cy_s = [None] * n
        gbx_s = gby_s = [None] * n
        if self._use_corr:
            _exch_t0 = time.perf_counter()
            if tracker is None:
                tracker = self._init_tracker(bcast)
            else:
                # no-op when already resident (every round after the
                # first); a cross-runtime resume hands us host/default-
                # device arrays that must land server-side
                tracker = jax.device_put(tracker, self._server)
            # fan-out: only LIVE shards' anchor-gradient programs are
            # dispatched; a fully-absent shard's rows are zero-filled
            # placeholders the active mask discards in favor of the
            # stale tracker rows
            gs = [
                self._shard_grads(bx, by, data, nk) if live else None
                for live, (bx, by), data, nk in zip(
                    shard_live, bcast, self._data_s, nk_slices
                )
            ]
            if not all(shard_live):
                # placeholders only when a shard actually skipped
                zx, zy = self._zero_shard_rows(x, y)
            gx = self._concat_server(
                [g[0] if g is not None else zx for g in gs]
            )
            gy = self._concat_server(
                [g[1] if g is not None else zy for g in gs]
            )
            full_state = self._gather_state()
            (
                cx, cy, gbar_x, gbar_y, new_state, tab_x, tab_y
            ) = self._server_exchange_elastic(
                gx, gy, full_state, active, tracker["gx"], tracker["gy"],
                agg.round_prev_active(active, prev_active),
            )
            tracker = {"gx": tab_x, "gy": tab_y}
            self._scatter_state(dict(new_state))
            cx_s = [
                jax.device_put(_slice_agents(cx, i * per, (i + 1) * per), d)
                for i, d in enumerate(self._shard_devices)
            ]
            cy_s = [
                jax.device_put(_slice_agents(cy, i * per, (i + 1) * per), d)
                for i, d in enumerate(self._shard_devices)
            ]
            gbx_s = [jax.device_put(gbar_x, d) for d in self._shard_devices]
            gby_s = [jax.device_put(gbar_y, d) for d in self._shard_devices]
            if tm is not None:
                # post-hoc span (the body stays un-nested): dispatch +
                # host time of the live shards' exchange fan-out
                tm.emit(
                    "span", "exchange_corrections",
                    seconds=time.perf_counter() - _exch_t0,
                    dispatches=sum(shard_live),
                )

        # local steps only on live shards: a shard that left this round
        # runs NOTHING (that is the elastic contract — its weight slice
        # is zero, so it has no aggregate share either)
        with maybe_span(tm, "local_steps", dispatches=sum(shard_live)):
            sums = [
                self._shard_steps_elastic(
                    bcast[i][0], bcast[i][1], self._data_s[i],
                    cx_s[i], cy_s[i], gbx_s[i], gby_s[i],
                    w_slices[i], b_slices[i], nk_slices[i],
                )
                for i in range(n)
                if shard_live[i]
            ]
        live = [d for d, on in zip(self._shard_devices, shard_live) if on]
        with maybe_span(tm, "aggregate"):
            x1, y1 = self._project(
                self._chain_sum([a for a, _ in sums], live),
                self._chain_sum([b for _, b in sums], live),
            )
        return x1, y1, tracker

    # ------------------------------------------------------------- reporting
    # `wire_report` comes from RunnerHistoryMixin (one owner for both
    # runtimes, schedule-aware); probes read the gathered state:
    def _telemetry_state(self) -> Dict:
        return self._gather_state()
