"""Pluggable communication strategies for the unified round engine.

The paper's FedGDA-GT (Algorithm 2) is one point in a family of federated
descent-ascent rounds that differ only along one axis: WHAT the agents
communicate each round and HOW local drift is corrected (cf. Sharma et al.
2022; Yang et al., SAGDA, 2022).  A `CommStrategy` captures that axis as
data; `repro.core.engine.make_round` consumes it and emits a round
function.  The engine reads only the hook protocol below, so strategies
and engine stay import-decoupled (strategies -> core.types plus the
kernels package for the fused compress-correction path).

Protocol consumed by the engine (all trace-time unless noted):
  sync_every_step    aggregate after EVERY local step (centralized GDA)
  use_correction     add a gradient-tracking correction to local steps
  exact_correction   correction cancels exactly at the anchor point, so
                     the fused-k0 trick applies (saves one grad eval)
  correction_dtype   optional reduced storage dtype for the correction
  stateful           round carries persistent cross-round state
  init_state(x,y,m)  build that state (RNG keys, error-feedback buffers)
  noise              optional `fed.noise.NoiseModel`: the local/anchor
                     gradient oracles become seeded stochastic draws;
                     None is the deterministic regime and elides every
                     noise primitive at trace time (bitwise legacy
                     rounds)
  sample_weights(state, m) -> (weights | None, state)   [traced]
  sample_noise_keys(state, m) -> (keys | None, state)   [traced]
                     one [m]-stacked per-agent key array per round from
                     the DEDICATED noise stream (`fed.noise`), folded by
                     global agent index so a sharded runtime can draw
                     once server-side and slice (never aliases the
                     sampling / compression "key" chains)
  transform_correction(cx, cy, state) -> (cx, cy, state) [traced]
                     cx/cy may come back as `transport.PackedTree` wire
                     payloads (objects with a `.decode()` hook) instead
                     of dense trees; the engine decodes before use
  transforms_correction  False when that transform is the identity, so
                     a sharded runtime may form each c_i on the agent's
                     own device (`fed.async_runtime`)
  rebase_state(state, active, prev_active) -> state  [traced]
                     re-anchor membership-dependent state for an elastic
                     round's active set (`repro.sim`): compressors zero
                     the error-feedback rows of agents that did not
                     participate last round, so a rejoining agent never
                     re-injects residuals of corrections it never
                     applied
  bytes_per_round(x, y, K)  analytic star-topology payload per agent
                     (`transport.measured_bytes_per_round` is the
                     empirical counterpart probing packed buffers)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine import agent_where, fixed_size_mask, renormalized_weights
from ..core.types import Pytree
from ..kernels.compress_correction import compress_leaf
from .noise import resolve_noise, noise_key as _noise_stream_key
from .transport import (
    LeafSpec,
    PackedTree,
    dense_payload_bytes as _payload_bytes,
    encode_leaf,
)

Weights = Optional[jax.Array]
State = dict


def _compressed_payload_bytes(tree: Pytree, ratio: float, bits: int = 32,
                              value_dtype=None) -> int:
    """Bytes for a `ratio`-sparsified, `bits`-bit stochastically
    quantized copy of `tree` (bits >= 32: sparsification only): kept
    values — bit-packed at the power-of-two storage width, padded to
    whole uint32 words per row, when quantizing — plus an integer index
    per kept value when sparsified (uint16 up to 2**16 columns, not a
    hard-coded 4 bytes) and ONE quantization scale per quantization
    GROUP — a last-axis row, exactly how `QuantizedGT` scales the grid.
    The layout arithmetic lives in `transport.LeafSpec` — the same
    object that shapes the packed encoder's buffers — so priced bytes
    equal packed buffer lengths by construction, and each leaf
    degenerates to the unquantized-sparse or dense encoding whenever
    that is cheaper.  `value_dtype` overrides the leaf dtype — the
    correction exchange is priced at the strategy's `correction_dtype`
    when one is set, since that is what the encoder actually packs."""
    return sum(
        LeafSpec.build(
            u.shape, value_dtype or u.dtype, ratio, bits
        ).wire_bytes()
        for u in jax.tree.leaves(tree)
    )


@dataclasses.dataclass(frozen=True)
class CommStrategy:
    """Base strategy: hook defaults shared by all concrete strategies."""

    # trace-time flags the engine dispatches on (class attributes, not
    # dataclass fields — concrete strategies override as needed)
    name = "base"
    sync_every_step = False
    use_correction = False
    correction_dtype: Any = None
    #: optional `fed.noise.NoiseModel` stochastic gradient oracle; None
    #: is the deterministic regime (bitwise-pinned legacy rounds)
    noise: Any = None
    #: seed of the dedicated noise stream (`fed.noise.noise_key` — a
    #: fold of NOISE_STREAM, never the raw PRNGKey(seed) the sampling /
    #: compression state chains from, so equal seeds cannot alias)
    noise_seed: int = 0

    @property
    def exact_correction(self) -> bool:
        # gradient noise voids the anchor-point cancellation: the
        # tracked gbar and the first local step see different draws
        return self.noise is None

    @property
    def stateful(self) -> bool:
        return self.noise is not None

    def _noise_state(self) -> State:
        """The noise stream's state entry (empty when deterministic) —
        concrete strategies merge this into their own `init_state`."""
        if self.noise is None:
            return {}
        return {"noise_key": _noise_stream_key(self.noise_seed)}

    def init_state(self, x: Pytree, y: Pytree, m: int) -> State:
        return self._noise_state()

    def sample_noise_keys(
        self, state: State, m: int
    ) -> Tuple[Optional[jax.Array], State]:
        """Per-agent noise keys for ONE round: split the dedicated
        stream once, then fold each agent's GLOBAL index into the round
        subkey — a sharded runtime samples this once server-side and
        hands each shard its slice, bit-identical to the fused path
        (`fed.noise` documents the full fold tree).  None when the
        strategy is deterministic."""
        if self.noise is None:
            return None, state
        state = dict(state)
        key, sub = jax.random.split(state["noise_key"])
        state["noise_key"] = key
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            sub, jnp.arange(m)
        )
        return keys, state

    def sample_noise_keys_ids(
        self, state: State, ids
    ) -> Tuple[Optional[jax.Array], State]:
        """`sample_noise_keys` for the sparse O(active) layout: the same
        one-split-per-round advance of the dedicated stream, but folding
        the given GLOBAL agent ids instead of arange(m) — an agent draws
        from the same stream whether its row lives at position `id` of a
        dense [m] stack or anywhere in an active-subset stack."""
        if self.noise is None:
            return None, state
        state = dict(state)
        key, sub = jax.random.split(state["noise_key"])
        state["noise_key"] = key
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            sub, jnp.asarray(ids)
        )
        return keys, state

    @property
    def sharded_state_keys(self) -> Tuple[str, ...]:
        """Top-level state entries whose leaves carry a leading per-agent
        axis.  A sharded runtime (`fed.async_runtime`, `launch.multihost`)
        stores these as per-shard slices living on the agents' devices
        instead of replicating the whole stack; everything else (sampling
        / rounding RNG keys) stays server-side."""
        return ()

    def sample_weights(self, state: State, m: int) -> Tuple[Weights, State]:
        """None means exact uniform averaging over all m agents (the
        bitwise-pinned legacy path); otherwise a length-m weight vector
        with sum(w) == 1 used for both gbar and the final aggregate."""
        return None, state

    #: whether `transform_correction` changes the corrections (or the
    #: state); False is the identity below
    transforms_correction = False

    def transform_correction(
        self, cx: Pytree, cy: Pytree, state: State
    ) -> Tuple[Pytree, Pytree, State]:
        return cx, cy, state

    def rebase_state(
        self, state: State, active, prev_active=None
    ) -> State:
        """Re-anchor membership-dependent state when an elastic schedule
        changes the active set (`repro.sim.ElasticAggregator` calls this
        each non-full round).  The base strategies carry no per-agent
        state that can go stale — corrections are re-formed from the
        current server iterate every round — so the default is a no-op."""
        del active, prev_active
        return state

    def realign_state_rows(self, state: State, prev_ids, ids) -> State:
        """`rebase_state` for the sparse O(active) layout, where the
        per-agent state entries (`sharded_state_keys`) carry one row per
        ACTIVE agent instead of one per population member.  Rows are
        re-gathered from last round's id layout into this round's: a
        continuing agent (present in both id lists) keeps its row, every
        other slot restarts at zero — exactly the dense rebase rule
        `keep = active & prev_active`, expressed over id lists.
        `prev_ids` None (first round / fresh start) zeroes everything,
        matching `init_state`'s zero buffers."""
        keys = [k for k in self.sharded_state_keys if k in state]
        if not keys:
            return state
        ids = np.asarray(ids)
        state = dict(state)
        if prev_ids is None or len(np.asarray(prev_ids)) == 0:
            pos = np.full(len(ids), -1, np.int64)
        else:
            prev_ids = np.asarray(prev_ids)
            # position of each current id in the previous (sorted) id
            # layout; -1 = not present last round
            idx = np.clip(
                np.searchsorted(prev_ids, ids), 0, len(prev_ids) - 1
            )
            pos = np.where(prev_ids[idx] == ids, idx, -1)
        pos_j = jnp.asarray(pos)
        keep = jnp.asarray(pos >= 0)

        def regather(t):
            def leaf(u):
                rows = jnp.take(u, jnp.maximum(pos_j, 0), axis=0)
                mask = keep.reshape((-1,) + (1,) * (rows.ndim - 1))
                return jnp.where(mask, rows, jnp.zeros_like(rows))

            return jax.tree.map(leaf, t)

        for k in keys:
            state[k] = regather(state[k])
        return state

    def bytes_per_round(self, x: Pytree, y: Pytree, num_local_steps: int) -> int:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FullSync(CommStrategy):
    """Centralized GDA: agents exchange gradients EVERY local step, so one
    'round' of K local steps costs K model up/downloads (paper Section 3.1,
    the K=1-equivalent baseline)."""

    name = "full_sync"
    sync_every_step = True

    def bytes_per_round(self, x, y, num_local_steps):
        return 2 * _payload_bytes((x, y)) * num_local_steps


@dataclasses.dataclass(frozen=True)
class LocalOnly(CommStrategy):
    """Local SGDA (Deng & Mahdavi 2021): K uncorrected local steps, then
    one model up/download.  Cheap but biased for K >= 2 (Proposition 1)."""

    name = "local_only"

    def bytes_per_round(self, x, y, num_local_steps):
        return 2 * _payload_bytes((x, y))


@dataclasses.dataclass(frozen=True)
class GradientTracking(CommStrategy):
    """FedGDA-GT (Algorithm 2): one gradient exchange per round buys the
    tracking correction c_i = gbar - g_i; linear convergence to the exact
    minimax point (Theorem 1).  `correction_dtype` optionally stores c_i
    reduced (e.g. float8_e4m3fn) to cut the +1-param-copy memory cost."""

    correction_dtype: Any = None
    name = "gradient_tracking"
    use_correction = True

    def bytes_per_round(self, x, y, num_local_steps):
        # up: grad + local model; down: global grad + averaged model
        return 4 * _payload_bytes((x, y))


@dataclasses.dataclass(frozen=True)
class PartialParticipation(GradientTracking):
    """Gradient tracking with client sampling: each round a uniform subset
    of S = max(1, round(participation*m)) agents participates; gbar and
    the aggregate are plain means over the sampled set (unbiased for the
    global mean under uniform sampling without replacement).

    participation >= 1 is the identity configuration: sampling is elided
    entirely and the round is EXACTLY GradientTracking.

    The subset draw itself is owned by `repro.sim.population` — this
    strategy is the degenerate Population (i.i.d. fixed-size sampling,
    no churn memory) expressed as a per-round weight sampler, and
    `sim.FixedSizeSampling` is the same draw expressed as an
    availability process (tests/test_population.py pins the two to the
    historical inline implementation bitwise)."""

    participation: float = 0.5
    seed: int = 0
    name = "partial_participation"

    @property
    def _sampling(self) -> bool:
        return self.participation < 1.0

    @property
    def stateful(self) -> bool:
        return self._sampling or self.noise is not None

    def init_state(self, x, y, m):
        state = self._noise_state()
        if self._sampling:
            # the sampling chain stays the raw PRNGKey(seed) it always
            # was (bitwise-pinned); only the noise stream is a fold
            state["key"] = jax.random.PRNGKey(self.seed)
        return state

    def sample_weights(self, state, m):
        if not self._sampling:
            return None, state
        S = max(1, int(round(self.participation * m)))
        if S >= m:
            return None, state
        state = dict(state)
        key, sub = jax.random.split(state["key"])
        state["key"] = key
        return renormalized_weights(fixed_size_mask(sub, m, S)), state

    def bytes_per_round(self, x, y, num_local_steps):
        # expected per-agent payload: only sampled agents communicate
        return int(round(self.participation * 4 * _payload_bytes((x, y))))


@dataclasses.dataclass(frozen=True)
class _CorrectionCompressor(CommStrategy):
    """Shared machinery for strategies that transform the tracking
    correction leaf-by-leaf — sparsification and/or stochastic
    quantization with error feedback.

    Concrete subclasses (CompressedGT, QuantizedGT) declare the knob
    fields and the `_ratio` / `_bits` hooks; this base owns the state
    layout (per-agent feedback buffers "ex"/"ey" + RNG "key"), the
    per-leaf transform loop, and the dispatch to the fused Pallas
    compress-correction kernel: lane-aligned 2D leaves take the fused
    VMEM pass when `use_kernel` is set, everything else falls back to
    the pure-jnp oracle (`repro.kernels.ref.compress_correction_ref`) —
    both paths are the same math on the same uniform draws, so the
    dispatch moves iterates by at most ~1 ulp.

    Each leaf is laid out as [m * rows, cols] with last-axis rows as the
    selection/quantization groups (`transport.wire_rows_cols`): vectors
    are one group per agent, matrices get per-channel scales and
    per-channel top-k — the same layout `bytes_per_round` prices.

    With `wire_transport` set, `transform_correction` returns
    `transport.PackedTree`s — REAL packed (value, index, scale) wire
    payloads — instead of dense masked trees; the engine scatter-adds
    them back on decode.  Both paths run identical math on identical
    draws, so wire on/off produces bitwise-identical GT iterates."""

    use_kernel: bool = False       # fused Pallas path on aligned 2D leaves
    wire_transport: bool = False   # emit packed payloads, not dense trees
    use_correction = True
    # knob defaults, overridden by concrete subclasses' dataclass fields
    mode = "topk"
    error_feedback = True
    seed = 0

    def __post_init__(self):
        if self.mode not in ("topk", "randk"):
            raise ValueError(f"unknown compression mode {self.mode!r}")

    # ------------------------------------------------------- knob hooks
    @property
    def _ratio(self) -> float:
        """Kept fraction of correction entries per leaf (1.0 = dense)."""
        raise NotImplementedError

    @property
    def _bits(self) -> int:
        """Stochastic-quantization bit-width (>= 32 = no quantization)."""
        return 32

    # ------------------------------------------------- derived structure
    @property
    def _sparsifying(self) -> bool:
        return self._ratio < 1.0

    @property
    def _quantizing(self) -> bool:
        return self._bits < 32

    @property
    def _active(self) -> bool:
        return self._sparsifying or self._quantizing

    @property
    def _needs_rng(self) -> bool:
        # rand-k selection scores and/or stochastic-rounding draws
        return self._quantizing or (self._sparsifying and self.mode == "randk")

    @property
    def exact_correction(self) -> bool:
        # any lossy transform (or gradient noise) voids the
        # anchor-point cancellation
        return not self._active and self.noise is None

    @property
    def transforms_correction(self) -> bool:
        return self._active

    @property
    def _compressor_state(self) -> bool:
        return self._active and (self.error_feedback or self._needs_rng)

    @property
    def stateful(self) -> bool:
        return self._compressor_state or self.noise is not None

    @property
    def sharded_state_keys(self) -> Tuple[str, ...]:
        # per-agent error-feedback buffers shard; the RNG key does not
        if self._active and self.error_feedback:
            return ("ex", "ey")
        return ()

    def init_state(self, x, y, m):
        state: State = self._noise_state()
        if not self._compressor_state:
            return state
        if self.error_feedback:
            # buffers live in the correction dtype (the engine casts the
            # correction before transform_correction, so residuals carry
            # that dtype — a mismatch would break the scan carry)
            zeros = lambda p: jax.tree.map(
                lambda u: jnp.zeros(
                    (m,) + u.shape, self.correction_dtype or u.dtype
                ),
                p,
            )
            state["ex"] = zeros(x)
            state["ey"] = zeros(y)
        if self._needs_rng:
            state["key"] = jax.random.PRNGKey(self.seed)
        return state

    def transform_correction(self, cx, cy, state):
        if not self._active:
            return cx, cy, state
        state = dict(state)
        sub = None
        if self._needs_rng:
            key, sub = jax.random.split(state["key"])
            state["key"] = key

        def compress(tree, err, tag):
            leaves, treedef = jax.tree.flatten(tree)
            eleaves = (
                jax.tree.leaves(err) if err is not None else [None] * len(leaves)
            )
            chat_leaves, resid_leaves = [], []
            payloads, specs, shapes = [], [], []
            for i, (c, e) in enumerate(zip(leaves, eleaves)):
                m = c.shape[0]
                spec = LeafSpec.build(
                    c.shape[1:], c.dtype, self._ratio, self._bits, self.mode
                )
                flat = c.reshape(m * spec.rows, spec.cols)
                k, n = spec.k, spec.cols
                leaf_key = (
                    None if sub is None else jax.random.fold_in(sub, 2 * i + tag)
                )
                u_sel = u_rnd = None
                if self.mode == "randk" and k < n:
                    u_sel = jax.random.uniform(
                        jax.random.fold_in(leaf_key, 0), flat.shape
                    )
                if self._quantizing:
                    u_rnd = jax.random.uniform(
                        jax.random.fold_in(leaf_key, 1), flat.shape
                    )
                e_flat = None if e is None else e.reshape(flat.shape)
                if self.wire_transport:
                    payload, resid = encode_leaf(
                        flat, e_flat, u_sel, u_rnd, spec.stacked(m),
                        use_kernel=self.use_kernel,
                    )
                    payloads.append(payload)
                    specs.append(spec.stacked(m))
                    shapes.append(c.shape)
                else:
                    chat, resid = compress_leaf(
                        flat,
                        e_flat,
                        u_sel,
                        u_rnd,
                        k=k,
                        bits=self._bits,
                        mode=self.mode,
                        use_kernel=self.use_kernel,
                    )
                    chat_leaves.append(chat.reshape(c.shape))
                resid_leaves.append(None if e is None else resid.reshape(c.shape))
            resid = (
                jax.tree.unflatten(treedef, resid_leaves)
                if err is not None
                else None
            )
            if self.wire_transport:
                chat = PackedTree(
                    payloads, specs, treedef, shapes,
                    use_kernel=self.use_kernel,
                )
            else:
                chat = jax.tree.unflatten(treedef, chat_leaves)
            return chat, resid

        ex = state.get("ex") if self.error_feedback else None
        ey = state.get("ey") if self.error_feedback else None
        cx, ex = compress(cx, ex, 0)
        cy, ey = compress(cy, ey, 1)
        if self.error_feedback:
            state["ex"], state["ey"] = ex, ey
        return cx, cy, state

    def rebase_state(self, state, active, prev_active=None):
        """Elastic re-anchoring of the error-feedback buffers: keep an
        agent's residual rows only if it participated BOTH last round
        (so the residual describes a correction it actually applied)
        and this round (so it is about to re-inject it).  Departed and
        rejoining agents restart from a zero residual — the compressed
        round they next see is anchored purely at the current server
        iterate.

        NOTE on prev_active=None: HERE it means "fresh start" (keep =
        active alone, matching a first round where every buffer is
        zero).  In `sim.elastic.tracker_exchange` the same None means
        "skip rebasing entirely" — the naive-server ablation — because
        there the hook is simply never called; use
        `ElasticAggregator.round_prev_active` to produce the right
        value rather than forwarding None through."""
        if "ex" not in state:
            return state
        keep = active if prev_active is None else (active & prev_active)
        zero_stale = lambda t: agent_where(
            keep, t, jax.tree.map(jnp.zeros_like, t)
        )
        state = dict(state)
        state["ex"] = zero_stale(state["ex"])
        state["ey"] = zero_stale(state["ey"])
        return state


@dataclasses.dataclass(frozen=True)
class CompressedGT(_CorrectionCompressor):
    """Gradient tracking with top-k / random-k sparsified corrections and
    (optional) error feedback.

    Each round the exact correction c_i = gbar - g_i is sparsified to a
    `compression_ratio` fraction of its entries before driving the local
    steps; what compression drops is accumulated in a per-agent feedback
    buffer e_i and re-injected next round (c_i + e_i is compressed, the
    residual becomes the new e_i) so the bias is compensated over time.
    Exactly k entries are kept per agent row (earliest index wins ties),
    so the kept fraction always matches what bytes_per_round prices.

    compression_ratio >= 1 is the identity configuration: compression is
    elided and the round is EXACTLY GradientTracking.  Ratios < 1 void
    the anchor-point cancellation, so the fused-k0 trick is disabled."""

    compression_ratio: float = 0.1
    mode: str = "topk"  # "topk" | "randk"
    error_feedback: bool = True
    seed: int = 0
    name = "compressed_gt"

    @property
    def _ratio(self) -> float:
        return self.compression_ratio

    def bytes_per_round(self, x, y, num_local_steps):
        # up: sparsified grad + local model; down: sparsified global grad +
        # averaged model (models stay dense; only the tracked-gradient
        # exchange is compressed)
        dense = _payload_bytes((x, y))
        return 2 * dense + 2 * _compressed_payload_bytes(
            (x, y), self.compression_ratio,
            value_dtype=self.correction_dtype,
        )


@dataclasses.dataclass(frozen=True)
class QuantizedGT(_CorrectionCompressor):
    """Gradient tracking with QSGD-style stochastically quantized (and
    optionally sparsified) corrections + error feedback (cf. Alistarh et
    al. 2017; the communication-complexity focus of SAGDA and Sharma et
    al. 2022 in PAPERS.md).

    The kept entries of each correction leaf are mapped to a symmetric
    `bits`-bit grid with a per-agent-row max-abs scale and rounded
    STOCHASTICALLY (floor + Bernoulli(frac)), so the quantizer is
    unbiased: E[Q(c)] = c.  The quantization error joins the
    sparsification residual in the error-feedback buffer.  `ratio` < 1
    additionally keeps only a top-k/rand-k fraction of entries before
    quantizing (compose both axes of compression).

    bits >= 32 AND ratio >= 1 is the identity configuration: the round
    is EXACTLY GradientTracking.  Any lossy setting voids the
    anchor-point cancellation, so the fused-k0 trick is disabled."""

    bits: int = 8
    ratio: float = 1.0
    mode: str = "topk"  # "topk" | "randk" (only used when ratio < 1)
    error_feedback: bool = True
    seed: int = 0
    name = "quantized_gt"

    def __post_init__(self):
        super().__post_init__()
        if self.bits < 2:
            raise ValueError(
                f"quantization needs bits >= 2 (sign + magnitude), got {self.bits}"
            )

    @property
    def _ratio(self) -> float:
        return self.ratio

    @property
    def _bits(self) -> int:
        return self.bits

    def bytes_per_round(self, x, y, num_local_steps):
        # up: quantized sparsified grad + local model; down: quantized
        # sparsified global grad + averaged model (models stay dense;
        # only the tracked-gradient exchange is compressed)
        dense = _payload_bytes((x, y))
        return 2 * dense + 2 * _compressed_payload_bytes(
            (x, y), self.ratio, self.bits,
            value_dtype=self.correction_dtype,
        )


@dataclasses.dataclass(frozen=True)
class SAGDA(GradientTracking):
    """Stochastic sampled averaged GDA (Yang et al. 2022, PAPERS.md):
    the gradient-tracking round driven by a stochastic gradient oracle —
    the anchor exchange AND every local step consume fresh draws from
    the dedicated noise stream, while the tracking correction
    c_i = gbar - g_i keeps the local drift centred on the (noisy) global
    direction.

    ``noise=None`` is the identity configuration: every noise primitive
    is elided at trace time (not zeroed at run time), so the round is
    BITWISE GradientTracking — tests/test_stochastic_parity.py pins it."""

    name = "sagda"


@dataclasses.dataclass(frozen=True)
class LocalSGDAPlus(CommStrategy):
    """Local SGDA+ (Sharma et al. 2022, PAPERS.md): Local SGDA's
    uncorrected K-step round with heavy-ball momentum on the local
    update direction (`optim.momentum.heavy_ball`; velocities are
    per-round, zero-initialized, so the round stays a pure function of
    the broadcast iterate) and a stochastic gradient oracle.

    ``momentum=0, noise=None`` is the identity configuration: the
    momentum carry and every noise primitive are elided at trace time,
    so the round is BITWISE LocalOnly."""

    momentum: float = 0.0
    name = "local_sgda_plus"

    def bytes_per_round(self, x, y, num_local_steps):
        # same cost model as LocalOnly: momentum state never leaves the
        # agent, so one model up/download per round
        return 2 * _payload_bytes((x, y))


# ------------------------------------------------------------------ registry
def _noise_kwargs(kw) -> dict:
    """Shared noise knobs for the stochastic-capable aliases; empty when
    the spec resolves to the deterministic regime, so identity configs
    construct bit-identical strategy dataclasses."""
    n = resolve_noise(
        kw.get("noise"),
        sigma=kw.get("noise_sigma"),
        fraction=kw.get("noise_fraction"),
    )
    if n is None:
        return {}
    return {"noise": n, "noise_seed": kw.get("noise_seed", 0)}


_ALIASES = {
    "gda": lambda kw: FullSync(),
    "sync_gda": lambda kw: FullSync(),
    "full_sync": lambda kw: FullSync(),
    "local_sgda": lambda kw: LocalOnly(),
    "local_only": lambda kw: LocalOnly(),
    "fedgda_gt": lambda kw: GradientTracking(
        correction_dtype=kw.get("correction_dtype"),
        **_noise_kwargs(kw),
    ),
    "gradient_tracking": lambda kw: GradientTracking(
        correction_dtype=kw.get("correction_dtype"),
        **_noise_kwargs(kw),
    ),
    "sagda": lambda kw: SAGDA(
        correction_dtype=kw.get("correction_dtype"),
        **_noise_kwargs(kw),
    ),
    "local_sgda_plus": lambda kw: LocalSGDAPlus(
        momentum=kw.get("momentum", 0.0),
        **_noise_kwargs(kw),
    ),
    "partial_gt": lambda kw: PartialParticipation(
        participation=kw.get("participation", 0.5),
        correction_dtype=kw.get("correction_dtype"),
        seed=kw.get("seed", 0),
        **_noise_kwargs(kw),
    ),
    "partial_participation": lambda kw: PartialParticipation(
        participation=kw.get("participation", 0.5),
        correction_dtype=kw.get("correction_dtype"),
        seed=kw.get("seed", 0),
        **_noise_kwargs(kw),
    ),
    "compressed_gt": lambda kw: CompressedGT(
        compression_ratio=kw.get("compression_ratio", 0.1),
        mode=kw.get("compression_mode", "topk"),
        error_feedback=kw.get("error_feedback", True),
        correction_dtype=kw.get("correction_dtype"),
        seed=kw.get("seed", 0),
        use_kernel=kw.get("use_kernel", False),
        wire_transport=kw.get("wire_transport", False),
        **_noise_kwargs(kw),
    ),
    "quantized_gt": lambda kw: QuantizedGT(
        bits=kw.get("quantization_bits", 8),
        ratio=kw.get("compression_ratio", 1.0),
        mode=kw.get("compression_mode", "topk"),
        error_feedback=kw.get("error_feedback", True),
        correction_dtype=kw.get("correction_dtype"),
        seed=kw.get("seed", 0),
        use_kernel=kw.get("use_kernel", False),
        wire_transport=kw.get("wire_transport", False),
        **_noise_kwargs(kw),
    ),
}


def resolve_strategy(spec, **kwargs) -> CommStrategy:
    """Map an algorithm name (or a ready strategy) to a CommStrategy.

    Accepts the legacy algorithm strings ("gda"/"sync_gda", "local_sgda",
    "fedgda_gt") plus the scenario-opening ones ("partial_gt",
    "compressed_gt", "quantized_gt") and the stochastic family ("sagda",
    "local_sgda_plus").  kwargs supply strategy hyperparameters
    (correction_dtype, participation, compression_ratio,
    quantization_bits, noise / noise_sigma / noise_fraction /
    noise_seed, momentum, ...).  The legacy strings ("gda",
    "local_sgda", "full_sync") stay deterministic oracles and ignore
    the noise knobs — the stochastic regime is opted into via the
    strategies that define it."""
    if isinstance(spec, CommStrategy):
        return spec
    try:
        factory = _ALIASES[spec]
    except (KeyError, TypeError):
        raise ValueError(f"unknown algorithm {spec!r}") from None
    return factory(kwargs)
