"""Phase-split federated minimax round engine.

One communication round of the generic federated descent-ascent template
is four **phases**, each a pure function over an explicit `RoundState`:

  broadcast             server ships (x^t, y^t) to the agents; a strategy
                        may sample participants (client-sampling weights)
  exchange_corrections  (if the strategy corrects drift) agents exchange
                        gradients once at the anchor point and form the
                        tracking correction c_i = gbar - g_i, possibly
                        transformed (reduced dtype, sparsification,
                        quantization, error feedback, packed wire payloads)
  local_steps           K local GDA steps, each adding c_i to the local
                        gradient (fused-k0 anchor step when the correction
                        is exact — see below)
  aggregate             server aggregates (weighted by participation) and
                        projects

`make_phases(loss, strategy, ...)` builds the four phase functions for a
strategy; `make_round` is their fused single-program composition and
reproduces the pre-split monolithic round BITWISE (the phase split only
reorganizes the trace — same primitives, same order; see
tests/test_phases.py and tests/test_engine_parity.py).  Runtimes that
dispatch phases separately — `repro.fed.async_runtime` drives per-agent-
shard `broadcast`/`local_steps` programs on their own devices and splits
`exchange_corrections` between the shards (anchor gradients) and the
server (transform) — consume the same phase functions, so there is one
oracle for the round math whatever the execution schedule.  Each phase
runs under `jax.named_scope(<its field name>)` and each loss-gradient
call under `jax.named_scope("loss_grad")`: HLO metadata only, so every
schedule's compiled program, and a profiler trace of it, can be split
by phase (`chipbench/scopes.py`).

The legacy constructors — `make_gda_step`, `make_local_sgda_round`,
`make_fedgda_gt_round` — remain thin wrappers over this engine with the
`FullSync` / `LocalOnly` / `GradientTracking` strategies.  Strategies are
duck-typed (`repro.fed.strategies.CommStrategy` is the reference
protocol), which keeps this module free of `repro.fed` imports.

Fused k=0 (§Perf, exact): when the correction is exact, the first local
gradient is evaluated at the same point as the tracking gradient, so
g_i + c_i == gbar and the step reduces to z <- z -/+ eta * gbar, saving
one full gradient evaluation per round.  Strategies whose corrections are
inexact (sparsified/quantized) report `exact_correction = False` and take
the literal K-step schedule instead.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .types import (
    LossFn,
    ProjFn,
    Pytree,
    grad_xy,
    identity_proj,
    tree_broadcast_agents,
)

#: sentinel distinguishing "no override" from an explicit None weight
#: override in `broadcast` (None means uniform averaging)
_UNSET = object()


def default_update(z: Pytree, g: Pytree, c: Pytree, eta, sign: float) -> Pytree:
    """z <- z + sign*eta*(g + c); sign=-1 descent (x), +1 ascent (y)."""
    return jax.tree.map(
        lambda u, gv, cv: u + sign * eta * (gv + cv.astype(gv.dtype)), z, g, c
    )


def agent_mean(tree: Pytree, weights) -> Pytree:
    """Uniform mean over the agent axis (weights None — the bitwise-pinned
    legacy path) or a weighted sum with participation weights."""
    if weights is None:
        return jax.tree.map(lambda u: jnp.mean(u, axis=0), tree)
    return jax.tree.map(
        lambda u: jnp.tensordot(weights.astype(u.dtype), u, axes=1), tree
    )


def agent_weighted_sum(tree: Pytree, weights) -> Pytree:
    """Partial aggregate of one agent SHARD: the weighted sum (weights
    None: plain sum — divide by the global m after combining shards).
    Shard runtimes combine these server-side; `agent_mean` is the
    single-program equivalent."""
    if weights is None:
        return jax.tree.map(lambda u: jnp.sum(u, axis=0), tree)
    return jax.tree.map(
        lambda u: jnp.tensordot(weights.astype(u.dtype), u, axes=1), tree
    )


def anchor_step(zs: Pytree, gbar: Pytree, eta, sign: float) -> Pytree:
    """The fused k=0 local step: every agent moves by the global gradient."""
    return jax.tree.map(
        lambda u, gb: u + sign * eta * gb[None].astype(u.dtype), zs, gbar
    )


def agent_where(mask, a: Pytree, b: Pytree) -> Pytree:
    """Per-agent select: leaves of `a` where the [m] mask holds, else
    `b`'s — the membership/budget gate of the elastic schedules (the
    mask broadcasts over every trailing leaf dimension)."""
    return jax.tree.map(
        lambda u, v: jnp.where(
            mask.reshape(mask.shape + (1,) * (u.ndim - 1)), u, v
        ),
        a,
        b,
    )


def fixed_size_mask(key: jax.Array, m: int, size: int) -> jax.Array:
    """Boolean mask with exactly `size` uniformly chosen agents active —
    the single owner of the fixed-size participation draw (uniform
    without replacement via permutation).  Lives here, below both
    `repro.fed` (PartialParticipation's sampler) and `repro.sim`
    (FixedSizeSampling's availability process), so neither layer imports
    the other for it."""
    sel = jax.random.permutation(key, m)[:size]
    return jnp.zeros((m,), bool).at[sel].set(True)


def renormalized_weights(active, dtype=None) -> jax.Array:
    """Uniform aggregation weights over the active set, re-normalized so
    they sum to 1 for ANY nonempty active set — the membership-aware
    server weighting (a naive server keeps 1/m and silently loses the
    departed agents' mass).  Accepts a boolean mask or 0/1 floats."""
    a = jnp.asarray(active).astype(dtype or jnp.result_type(float))
    return a / jnp.sum(a)


def pod_weighted_sums(
    tree: Pytree, weights: jax.Array, pod_ids: jax.Array, num_pods: int
) -> Pytree:
    """Level one of the two-level agent -> pod -> server aggregation
    tree: per-pod partial weighted sums via segment-sum over the agents'
    pod assignments (`pod_ids`, [n] int — `sim.PodMap.pod_of` of the
    active ids).  Leaves gain a leading [num_pods] axis; summing it
    (`pods_total`) recovers the flat weighted sum to fp tolerance —
    Σ_p Σ_{i∈p} w_i u_i vs Σ_i w_i u_i differ only in reduction order
    (tests/test_sparse_elastic.py pins the property)."""

    def seg(u):
        w = weights.astype(u.dtype)
        uw = u * w.reshape((-1,) + (1,) * (u.ndim - 1))
        return jax.ops.segment_sum(uw, pod_ids, num_segments=num_pods)

    return jax.tree.map(seg, tree)


def pods_total(pod_tree: Pytree) -> Pytree:
    """Level two: the server's sum over the pod axis of the partial
    aggregates from `pod_weighted_sums` (quiet pods contribute exact
    zeros, so skipping them is a no-op on the value)."""
    return jax.tree.map(lambda u: jnp.sum(u, axis=0), pod_tree)


def tracking_corrections(
    gx: Pytree, gy: Pytree, gbar_x: Pytree, gbar_y: Pytree, cdt=None
):
    """The raw tracking corrections c_i = gbar - g_i per agent, optionally
    stored reduced (`cdt`).  One owner for the formation across every
    schedule: the fused exchange phase, the async runtime's server
    exchange and the multi-host shard encode all call this."""

    def corr(gbar, gi):
        c = gbar[None] - gi
        if cdt is not None:
            c = c.astype(cdt)
        return c

    return jax.tree.map(corr, gbar_x, gx), jax.tree.map(corr, gbar_y, gy)


def noise_eval_keys(noise_keys: jax.Array, idx) -> jax.Array:
    """Per-agent evaluation keys for ONE stochastic gradient call: fold
    the in-round call index (0 = the anchor exchange, 1 + k = local step
    k) into each agent's per-round noise key.  Single owner of the
    eval-level fold, shared by the fused round, the elastic round and
    the async shard programs so every schedule consumes the exact same
    draws (the full fold tree is documented in `repro.fed.noise`)."""
    return jax.vmap(jax.random.fold_in, in_axes=(0, None))(noise_keys, idx)


def make_noise_vgrad(gfn: Callable, noise) -> Callable:
    """vmapped per-agent stochastic gradient oracle for a noise model
    (duck-typed on `.grad(gfn, key, x, y, data)` — see
    `repro.fed.noise.NoiseModel`; this module stays free of `repro.fed`
    imports).  Signature: `(keys[m], xs, ys, agent_data) -> SaddleField`
    — the stochastic counterpart of `jax.vmap(gfn, (0, 0, 0))`."""

    def one(key, xi, yi, di):
        return noise.grad(gfn, key, xi, yi, di)

    return jax.vmap(one, in_axes=(0, 0, 0, 0))


# kept as private aliases — pre-split internal names, still referenced by
# downstream forks of the monolithic engine
_agent_mean = agent_mean
_anchor_step = anchor_step


@dataclasses.dataclass
class RoundState:
    """Explicit state threaded through the round phases.

    A registered-dataclass pytree, so separately-jitted phase programs can
    take and return it directly; `fused` is static metadata (it gates
    whether `local_steps` takes the anchor shortcut and must be known at
    trace time).

    Fields are populated progressively: `broadcast` fills xs/ys/weights
    (plus the elastic schedule's step_budgets/active when a runner passes
    them), `exchange_corrections` fills cx/cy/gbar_x/gbar_y/fused,
    `local_steps` advances xs/ys, `aggregate` consumes the lot.  Unused
    fields stay None (empty subtrees)."""

    x: Pytree                      # global iterates at round start
    y: Pytree
    state: Pytree                  # strategy state (RNG, EF buffers)
    xs: Pytree = None              # per-agent iterates [m, ...]
    ys: Pytree = None
    weights: Optional[jax.Array] = None  # participation weights (None=uniform)
    cx: Pytree = None              # tracking corrections [m, ...]
    cy: Pytree = None
    gbar_x: Pytree = None          # anchor-point global gradients
    gbar_y: Pytree = None
    step_budgets: Optional[jax.Array] = None  # [m] local-step caps (None=K)
    active: Optional[jax.Array] = None        # [m] availability mask
    noise_keys: Optional[jax.Array] = None    # [m] per-round noise keys
    #: GLOBAL agent ids of the rows in this state ([n] int64) — None on
    #: the dense path (row i IS agent i).  The sparse O(active) runtime
    #: threads the round's active id list here so id-keyed draws (noise
    #: stream folds) hit the same per-agent streams as the dense layout
    active_indices: Optional[jax.Array] = None
    fused: bool = False            # static: anchor shortcut applies


jax.tree_util.register_dataclass(
    RoundState,
    data_fields=(
        "x", "y", "state", "xs", "ys", "weights",
        "cx", "cy", "gbar_x", "gbar_y", "step_budgets", "active",
        "noise_keys", "active_indices",
    ),
    meta_fields=("fused",),
)


class RoundPhases(NamedTuple):
    """The four phase functions for one strategy (see module docstring).

    broadcast(x, y, agent_data, state, *, weights=...,
              step_budgets=None, active=None, noise_keys=...) -> RoundState
    exchange_corrections(rs, agent_data) -> RoundState
    local_steps(rs, agent_data) -> RoundState
    aggregate(rs) -> (x1, y1, state)

    Each is pure and shard-agnostic: the agent count is read from
    `agent_data` at trace time, so the same functions serve the fused
    single-program round (`make_round`) and per-shard dispatch
    (`fed.async_runtime`).  `broadcast`'s keyword-only `weights` lets a
    sharded runtime sample participation ONCE server-side and feed each
    shard its slice instead of re-sampling per shard; `step_budgets` and
    `active` carry an elastic schedule's per-agent local-step caps and
    availability mask (`repro.sim`) — `local_steps` freezes an agent
    once its budget is spent, and `None` (the default) is the pinned
    legacy trace with no gating primitives at all.  `noise_keys` works
    like `weights`: left unset, a stochastic strategy samples its
    per-agent keys from the dedicated noise stream in `state`; a
    sharded runtime samples once server-side and feeds each shard its
    slice (None — explicit — means deterministic, e.g. tracker init)."""

    broadcast: Callable
    exchange_corrections: Callable
    local_steps: Callable
    aggregate: Callable


def _scoped(name: str, fn: Callable) -> Callable:
    """`fn` with every operation it traces under `jax.named_scope(name)`:
    HLO metadata only (`op_name` paths), so values and fusions are
    unchanged, and a profiler trace of the compiled program can be split
    by the name (`chipbench/scopes.py`)."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return scoped


def _named_phases(*fns: Callable) -> RoundPhases:
    """The four phases, each under the scope of its field name."""
    return RoundPhases(*(_scoped(n, f) for n, f in zip(RoundPhases._fields, fns)))


def _num_agents(agent_data: Pytree) -> int:
    return jax.tree.leaves(agent_data)[0].shape[0]


def make_phases(
    loss: LossFn,
    strategy,
    num_local_steps: int,
    eta_x: float,
    eta_y: Optional[float] = None,
    *,
    proj_x: ProjFn = identity_proj,
    proj_y: ProjFn = identity_proj,
    update_fn: Callable = default_update,
    constrain_agents: Optional[Callable] = None,
) -> RoundPhases:
    """Build the four round phases for `strategy` (see RoundPhases)."""
    if eta_y is None:
        eta_y = eta_x
    gfn = grad_xy(loss)

    if getattr(strategy, "sync_every_step", False):
        # FullSync: K communicated steps, each a centralized GDA update.
        # There is no per-agent divergence to broadcast or correct, so
        # broadcast/exchange are identities and the whole round lives in
        # local_steps (each "local" step IS a global aggregate).
        vg = _scoped("loss_grad", jax.vmap(gfn, in_axes=(None, None, 0)))

        def gda_step(x, y, agent_data, weights=None):
            g = vg(x, y, agent_data)
            gx = agent_mean(g.gx, weights)
            gy = agent_mean(g.gy, weights)
            x1 = proj_x(jax.tree.map(lambda u, v: u - eta_x * v, x, gx))
            y1 = proj_y(jax.tree.map(lambda u, v: u + eta_y * v, y, gy))
            return x1, y1

        def broadcast(x, y, agent_data, state, *, weights=_UNSET,
                      step_budgets=None, active=None, noise_keys=_UNSET,
                      active_indices=None):
            # every "local" step is a global aggregate, so there is no
            # per-agent divergence to budget — step_budgets is ignored;
            # an elastic schedule's membership enters through `weights`.
            # FullSync is a deterministic baseline: noise_keys accepted
            # for signature uniformity, never consumed
            del agent_data, step_budgets, noise_keys
            w = None if weights is _UNSET else weights
            return RoundState(x=x, y=y, state=state, weights=w, active=active,
                              active_indices=active_indices)

        def exchange_corrections(rs, agent_data):
            del agent_data
            return rs

        def local_steps(rs, agent_data):
            x, y, w = rs.x, rs.y, rs.weights
            if num_local_steps == 1:
                x, y = gda_step(x, y, agent_data, w)
            else:
                (x, y), _ = jax.lax.scan(
                    lambda c, _: (gda_step(*c, agent_data, w), None),
                    (x, y),
                    None,
                    length=num_local_steps,
                )
            return dataclasses.replace(rs, x=x, y=y)

        def aggregate(rs):
            return rs.x, rs.y, rs.state

        return _named_phases(broadcast, exchange_corrections, local_steps, aggregate)

    vgrad = _scoped("loss_grad", jax.vmap(gfn, in_axes=(0, 0, 0)))
    use_corr = bool(getattr(strategy, "use_correction", False))
    cdt = getattr(strategy, "correction_dtype", None)
    # stochastic knobs — None / 0.0 are trace-time identities: the
    # deterministic path below keeps the exact legacy primitives (no
    # zeroed noise, no 0-scaled velocity — bitwise-pinned)
    noise = getattr(strategy, "noise", None)
    momentum = float(getattr(strategy, "momentum", 0.0) or 0.0)
    nvgrad = (_scoped("loss_grad", make_noise_vgrad(gfn, noise))
              if noise is not None else None)
    if momentum:
        # lazy: optim.momentum imports core — only the momentum round
        # needs the shared heavy-ball primitive
        from ..optim.momentum import heavy_ball

    def broadcast(x, y, agent_data, state, *, weights=_UNSET,
                  step_budgets=None, active=None, noise_keys=_UNSET,
                  active_indices=None):
        m = _num_agents(agent_data)
        if weights is _UNSET:
            weights, state = strategy.sample_weights(state, m)
        if noise_keys is _UNSET:
            noise_keys = None
            if noise is not None:
                if active_indices is not None:
                    # sparse layout: rows are the active subset — fold
                    # the GLOBAL ids so each agent sees the same noise
                    # stream it would in the dense [m] layout
                    noise_keys, state = strategy.sample_noise_keys_ids(
                        state, active_indices
                    )
                else:
                    noise_keys, state = strategy.sample_noise_keys(state, m)
        xs = tree_broadcast_agents(x, m)
        ys = tree_broadcast_agents(y, m)
        if constrain_agents is not None:
            xs, ys = constrain_agents(xs, ys)
        return RoundState(
            x=x, y=y, state=state, xs=xs, ys=ys, weights=weights,
            step_budgets=step_budgets, active=active, noise_keys=noise_keys,
            active_indices=active_indices,
        )

    def exchange_corrections(rs, agent_data):
        if not use_corr:
            return rs
        m = _num_agents(agent_data)
        state = rs.state
        if m > 1:
            # one gradient exchange at the anchor point (eval index 0 of
            # the noise stream when stochastic)
            if noise is None or rs.noise_keys is None:
                g0 = vgrad(rs.xs, rs.ys, agent_data)
            else:
                g0 = nvgrad(
                    noise_eval_keys(rs.noise_keys, 0),
                    rs.xs, rs.ys, agent_data,
                )
            gbar_x = agent_mean(g0.gx, rs.weights)
            gbar_y = agent_mean(g0.gy, rs.weights)
            cx, cy = tracking_corrections(g0.gx, g0.gy, gbar_x, gbar_y, cdt)
            cx, cy, state = strategy.transform_correction(cx, cy, state)
            # wire-transport strategies hand back PACKED payloads
            # (repro.fed.transport.PackedTree — duck-typed on the
            # `decode` hook to keep the engine import-decoupled):
            # the server gathers the packed buffers and scatter-adds
            # them back to dense corrections before the local steps
            if hasattr(cx, "decode"):
                cx = cx.decode()
            if hasattr(cy, "decode"):
                cy = cy.decode()
            # momentum folds the correction into a velocity, so the
            # first step is no longer the plain anchor update
            fused = bool(strategy.exact_correction) and not momentum
            return dataclasses.replace(
                rs, cx=cx, cy=cy, gbar_x=gbar_x, gbar_y=gbar_y,
                fused=fused, state=state,
            )
        # m == 1: the correction is identically zero and elided
        cx = jax.tree.map(jnp.zeros_like, rs.xs)
        cy = jax.tree.map(jnp.zeros_like, rs.ys)
        return dataclasses.replace(rs, cx=cx, cy=cy)

    def local_steps(rs, agent_data):
        xs, ys = rs.xs, rs.ys
        budgets = rs.step_budgets
        stochastic = noise is not None and rs.noise_keys is not None

        def grads(xs, ys, k):
            # k is the in-round step index; the stochastic oracle draws
            # at eval index 1 + k (0 belongs to the anchor exchange)
            if not stochastic:
                return vgrad(xs, ys, agent_data)
            return nvgrad(
                noise_eval_keys(rs.noise_keys, 1 + k), xs, ys, agent_data
            )

        if use_corr:
            cx, cy = rs.cx, rs.cy

            def step_once(xs, ys, k):
                g = grads(xs, ys, k)
                xs = update_fn(xs, g.gx, cx, eta_x, -1.0)
                ys = update_fn(ys, g.gy, cy, eta_y, +1.0)
                if constrain_agents is not None:
                    # re-anchor the scan carry's sharding every step
                    xs, ys = constrain_agents(xs, ys)
                return xs, ys

        else:

            def step_once(xs, ys, k):
                g = grads(xs, ys, k)
                xs = jax.tree.map(lambda u, v: u - eta_x * v, xs, g.gx)
                ys = jax.tree.map(lambda u, v: u + eta_y * v, ys, g.gy)
                return xs, ys

        start = 0
        if rs.fused:
            xs1 = anchor_step(xs, rs.gbar_x, eta_x, -1.0)
            ys1 = anchor_step(ys, rs.gbar_y, eta_y, +1.0)
            if constrain_agents is not None:
                xs1, ys1 = constrain_agents(xs1, ys1)
            if budgets is None:
                xs, ys = xs1, ys1
            else:
                live = budgets >= 1
                xs = agent_where(live, xs1, xs)
                ys = agent_where(live, ys1, ys)
            start = 1
        if num_local_steps - start > 0:
            if momentum:
                # heavy-ball local steps (Local SGDA+): per-round
                # velocities, zero-initialized, carrying the corrected
                # step direction; budget gating freezes iterate AND
                # velocity so a spent agent's round contribution is
                # exactly its last live step
                def eff(g, c):
                    if c is None:
                        return g
                    return jax.tree.map(
                        lambda gv, cv: gv + cv.astype(gv.dtype), g, c
                    )

                def mom_body(carry, k):
                    xs, ys, vx, vy = carry
                    g = grads(xs, ys, k)
                    vx1 = heavy_ball(vx, eff(g.gx, cx if use_corr else None),
                                     momentum)
                    vy1 = heavy_ball(vy, eff(g.gy, cy if use_corr else None),
                                     momentum)
                    xs1 = jax.tree.map(lambda u, v: u - eta_x * v, xs, vx1)
                    ys1 = jax.tree.map(lambda u, v: u + eta_y * v, ys, vy1)
                    if constrain_agents is not None:
                        xs1, ys1 = constrain_agents(xs1, ys1)
                    if budgets is None:
                        return (xs1, ys1, vx1, vy1), None
                    live = k < budgets
                    return (
                        agent_where(live, xs1, xs),
                        agent_where(live, ys1, ys),
                        agent_where(live, vx1, vx),
                        agent_where(live, vy1, vy),
                    ), None

                zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
                (xs, ys, _, _), _ = jax.lax.scan(
                    mom_body,
                    (xs, ys, zeros(xs), zeros(ys)),
                    jnp.arange(start, num_local_steps),
                )
            elif budgets is None and not stochastic:
                # the pinned legacy trace: no gating or indexing
                # primitives at all
                (xs, ys), _ = jax.lax.scan(
                    lambda c, _: (step_once(*c, 0), None),
                    (xs, ys),
                    None,
                    length=num_local_steps - start,
                )
            elif budgets is None:
                # stochastic, full budgets: the scan indexes the noise
                # stream by step but gates nothing
                (xs, ys), _ = jax.lax.scan(
                    lambda c, k: (step_once(*c, k), None),
                    (xs, ys),
                    jnp.arange(start, num_local_steps),
                )
            else:
                # elastic: step k only advances agents whose budget still
                # covers it — a spent (or absent, budget 0) agent's
                # iterate is frozen so its weighted aggregate share (and
                # its zero weight, for inactive agents) stays exact
                def gated(carry, k):
                    xs, ys = carry
                    xs1, ys1 = step_once(xs, ys, k)
                    live = k < budgets
                    return (
                        agent_where(live, xs1, xs),
                        agent_where(live, ys1, ys),
                    ), None

                (xs, ys), _ = jax.lax.scan(
                    gated, (xs, ys), jnp.arange(start, num_local_steps)
                )
        return dataclasses.replace(rs, xs=xs, ys=ys)

    def aggregate(rs):
        x1 = proj_x(agent_mean(rs.xs, rs.weights))
        y1 = proj_y(agent_mean(rs.ys, rs.weights))
        return x1, y1, rs.state

    return _named_phases(broadcast, exchange_corrections, local_steps, aggregate)


def make_round(
    loss: LossFn,
    strategy,
    num_local_steps: int,
    eta_x: float,
    eta_y: Optional[float] = None,
    *,
    proj_x: ProjFn = identity_proj,
    proj_y: ProjFn = identity_proj,
    update_fn: Callable = default_update,
    constrain_agents: Optional[Callable] = None,
    explicit_state: Optional[bool] = None,
) -> Callable:
    """Build one communication round for `strategy`: the fused
    single-program composition of the four phases (`make_phases`).

    Returns `round(x, y, agent_data) -> (x, y)` for stateless strategies.
    Stateful strategies (client sampling RNG, error-feedback buffers)
    return `round(x, y, agent_data, state) -> (x, y, state)` with the
    initial state from `strategy.init_state(x, y, m)`; pass
    `explicit_state=True` to force that signature for stateless
    strategies too (useful when mixing strategies under one scan).
    """
    stateful = bool(getattr(strategy, "stateful", False))
    if explicit_state is None:
        explicit_state = stateful
    if stateful and not explicit_state:
        raise ValueError(
            f"strategy {strategy!r} carries cross-round state; build with "
            "explicit_state=True and thread `state` through the rounds"
        )
    phases = make_phases(
        loss,
        strategy,
        num_local_steps,
        eta_x,
        eta_y,
        proj_x=proj_x,
        proj_y=proj_y,
        update_fn=update_fn,
        constrain_agents=constrain_agents,
    )

    def core(x, y, agent_data, state):
        rs = phases.broadcast(x, y, agent_data, state)
        rs = phases.exchange_corrections(rs, agent_data)
        rs = phases.local_steps(rs, agent_data)
        return phases.aggregate(rs)

    if explicit_state:
        return core

    def round(x, y, agent_data):
        x1, y1, _ = core(x, y, agent_data, {})
        return x1, y1

    return round


def run_strategy_rounds(
    round_fn: Callable,
    x0: Pytree,
    y0: Pytree,
    agent_data: Pytree,
    num_rounds: int,
    state0: Optional[Pytree] = None,
    metric_fn: Optional[Callable] = None,
):
    """Scan a stateful round (built with `explicit_state=True`) for
    `num_rounds`, threading the strategy state through the carry.

    Returns ((x, y, state), metrics) with metrics evaluated on the input
    of each round plus once at the end — the stateful counterpart of
    `repro.core.gda.run_rounds`."""
    if state0 is None:
        state0 = {}

    def body(carry, _):
        x, y, s = carry
        meas = metric_fn(x, y) if metric_fn is not None else None
        x1, y1, s1 = round_fn(x, y, agent_data, s)
        return (x1, y1, s1), meas

    (x, y, s), metrics = jax.lax.scan(
        body, (x0, y0, state0), None, length=num_rounds
    )
    if metric_fn is not None:
        final = metric_fn(x, y)
        metrics = jax.tree.map(
            lambda hist, last: jnp.concatenate([hist, last[None]]), metrics, final
        )
    return (x, y, s), metrics
