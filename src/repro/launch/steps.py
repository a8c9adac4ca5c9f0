"""SPMD step builders: federated minimax train_step + prefill/decode serve_step.

train_step = ONE federated communication round lowered as a single jitted
SPMD program on the production mesh, built by the phase-split round
engine (`repro.core.engine.make_round` — the fused composition of
broadcast / exchange_corrections / local_steps / aggregate) for any
`CommStrategy` — FedGDA-GT by default; baselines (local_sgda, sync_gda)
and the scenario strategies (partial_gt, compressed_gt, quantized_gt)
share the same signature so the dry-run can compare their collective
schedules directly.  Stateful strategies thread their state as an extra
replicated step input.

The async runtime executes the same phases as separately dispatched
per-shard programs plus a server-side packed-payload gather;
`build_gather_decode_train_step` lowers that gather on the production
mesh (payload buffers sharded over the fed axes, decode replicated) so
the dry-run can census its all-gather bytes against
`measured_bytes_per_round` (`--runtime async`, tag `__async`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..core.engine import make_round
from ..fed.strategies import CommStrategy, resolve_strategy
from ..models import batch_struct, init_caches, init_params
from ..models.transformer import embed_inputs, forward, logits_from_hidden
from ..problems.adversarial import delta_projection, make_adversarial_loss
from .mesh import fed_axes, num_agents
from .shardings import (
    cache_shardings,
    make_agent_constraint,
    param_shardings,
    replicated,
    serve_batch_sharding,
    train_batch_shardings,
)

Pytree = Any

_CORRECTION_DTYPES = {"float8_e4m3fn": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16}


def abstract_params(cfg: ModelConfig, dtype) -> Pytree:
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype)
    )


def abstract_caches(cfg: ModelConfig, batch: int, capacity: int, dtype) -> Pytree:
    return jax.eval_shape(lambda: init_caches(cfg, batch, capacity, dtype))


def delta_struct(cfg: ModelConfig, dtype) -> Dict:
    return {"delta": jax.ShapeDtypeStruct((cfg.d_model,), dtype)}


# --------------------------------------------------------------------------
# training (one federated communication round)
# --------------------------------------------------------------------------
def train_input_specs(
    cfg: ModelConfig, shape: ShapeConfig, mesh, dtype=jnp.bfloat16
) -> Dict:
    """ShapeDtypeStructs for (x_global, y_global, agent_batches)."""
    m = num_agents(mesh, cfg.fed_mode)
    assert shape.global_batch % m == 0, (shape.global_batch, m)
    b_local = shape.global_batch // m
    one = batch_struct(cfg, b_local, shape.seq_len, dtype)
    agent_batches = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((m,) + s.shape, s.dtype), one
    )
    return {
        "x": abstract_params(cfg, dtype),
        "y": delta_struct(cfg, dtype),
        "batch": agent_batches,
    }


def _resolve_cfg_strategy(cfg: ModelConfig, algorithm) -> CommStrategy:
    """One owner for the cfg-knob -> strategy resolution, shared by the
    fused train step and the async gather-census step."""
    kw = dict(
        correction_dtype=_CORRECTION_DTYPES.get(cfg.correction_dtype),
        participation=cfg.participation,
        compression_ratio=cfg.compression_ratio,
        quantization_bits=cfg.quantization_bits,
        wire_transport=cfg.wire_transport,
        momentum=cfg.momentum,
    )
    # gate on the cfg knob, not on sigma/fraction: resolve_noise treats a
    # bare nonzero sigma as gaussian, and the defaults (0.1/0.5) would
    # otherwise silently make every config stochastic
    if cfg.noise != "none":
        kw.update(
            noise=cfg.noise,
            noise_sigma=cfg.noise_sigma,
            noise_fraction=cfg.noise_fraction,
            noise_seed=cfg.noise_seed,
        )
    return resolve_strategy(algorithm, **kw)


def _traced_on(mesh, fn: Callable) -> Callable:
    """`fn` traced under `mesh`'s abstract mesh, so that what it calls
    sees that the program is partitioned over the mesh: the model keeps
    its jnp paths there in place of a Pallas kernel that cannot be
    partitioned automatically (`kernels.backend.on_one_tpu`).  The mesh
    context changes nothing else in the lowered program."""

    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return traced


def build_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    algorithm="fedgda_gt",  # legacy name or a CommStrategy instance
    num_local_steps: int = 4,
    eta: float = 1e-3,
    delta_radius: float = 1.0,
    dtype=jnp.bfloat16,
    remat: bool = True,
    sequence_parallel: bool = True,
    sharding_variant: str = "baseline",
    h_shard: Optional[str] = None,  # overrides sequence_parallel: seq|batch|none
    q_block: Optional[int] = None,  # overrides cfg.q_block
) -> Tuple[Callable, Callable]:
    """Returns (jitted_step, input_specs_fn)."""
    import dataclasses as _dc

    if q_block:
        cfg = _dc.replace(cfg, q_block=q_block)
    if h_shard is None:
        h_shard = "seq" if sequence_parallel else "none"
    inner = "data" if cfg.fed_mode == "B" else None
    h_sh = None
    if h_shard == "seq":
        h_sh = NamedSharding(mesh, P(inner, "model", None))
    elif h_shard == "batch":
        h_sh = NamedSharding(mesh, P("model", None, None))
    loss = make_adversarial_loss(cfg, remat=remat, h_sharding=h_sh)
    proj_y = delta_projection(delta_radius)
    constrain = make_agent_constraint(cfg, mesh, None, sharding_variant)
    strategy = _resolve_cfg_strategy(cfg, algorithm)
    stateful = strategy.stateful
    rnd = _traced_on(mesh, make_round(
        loss,
        strategy,
        num_local_steps,
        eta,
        proj_y=proj_y,
        constrain_agents=constrain,
        explicit_state=stateful,
    ))

    x_sh = param_shardings(abstract_params(cfg, dtype), cfg, mesh, sharding_variant)
    y_sh = jax.tree.map(lambda _: replicated(mesh), delta_struct(cfg, dtype))
    bsh = train_batch_shardings(cfg, mesh)
    batch_sh_fn = lambda tree: jax.tree.map(lambda s: bsh(len(s.shape)), tree)

    def specs_fn(shape: ShapeConfig, dt=dtype):
        sp = train_input_specs(cfg, shape, mesh, dt)
        if stateful:
            # strategy state (sampling RNG / error-feedback buffers) rides
            # along as a fourth, replicated step input
            m = num_agents(mesh, cfg.fed_mode)
            sp["state"] = jax.eval_shape(
                lambda xx, yy: strategy.init_state(xx, yy, m), sp["x"], sp["y"]
            )
        return sp

    def jitted(shape: ShapeConfig):
        sp = specs_fn(shape)
        if stateful:
            st_sh = jax.tree.map(lambda _: replicated(mesh), sp["state"])
            return jax.jit(
                rnd,
                in_shardings=(x_sh, y_sh, batch_sh_fn(sp["batch"]), st_sh),
                out_shardings=(x_sh, y_sh, st_sh),
                donate_argnums=(0,),
            )
        return jax.jit(
            rnd,
            in_shardings=(x_sh, y_sh, batch_sh_fn(sp["batch"])),
            out_shardings=(x_sh, y_sh),
            donate_argnums=(0,),
        )

    return jitted, specs_fn


def build_elastic_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    algorithm="fedgda_gt",
    num_local_steps: int = 4,
    eta: float = 1e-3,
    delta_radius: float = 1.0,
    dtype=jnp.bfloat16,
    remat: bool = True,
    sequence_parallel: bool = True,
    sharding_variant: str = "baseline",
    h_shard: Optional[str] = None,
    q_block: Optional[int] = None,
) -> Tuple[Callable, Callable]:
    """The membership-aware elastic round (`repro.sim.make_elastic_round`)
    as one SPMD program: `build_train_step`'s signature plus the
    schedule inputs — tracker table (per-agent anchor gradients, agent
    axis over the fed axes like the batch), weights / budgets / active
    (tiny [m] vectors, replicated).  This is what a `--population`
    dry-run lowers: the collective schedule of a round that must gate
    local steps and re-normalize the aggregate per membership."""
    import dataclasses as _dc

    from ..sim.elastic import make_elastic_round

    if q_block:
        cfg = _dc.replace(cfg, q_block=q_block)
    if h_shard is None:
        h_shard = "seq" if sequence_parallel else "none"
    inner = "data" if cfg.fed_mode == "B" else None
    h_sh = None
    if h_shard == "seq":
        h_sh = NamedSharding(mesh, P(inner, "model", None))
    elif h_shard == "batch":
        h_sh = NamedSharding(mesh, P("model", None, None))
    loss = make_adversarial_loss(cfg, remat=remat, h_sharding=h_sh)
    proj_y = delta_projection(delta_radius)
    constrain = make_agent_constraint(cfg, mesh, None, sharding_variant)
    strategy = _resolve_cfg_strategy(cfg, algorithm)
    rnd = _traced_on(mesh, make_elastic_round(
        loss,
        strategy,
        num_local_steps,
        eta,
        proj_y=proj_y,
        constrain_agents=constrain,
    ))

    m = num_agents(mesh, cfg.fed_mode)
    fa = fed_axes(mesh, cfg.fed_mode)
    x_sh = param_shardings(abstract_params(cfg, dtype), cfg, mesh, sharding_variant)
    y_sh = jax.tree.map(lambda _: replicated(mesh), delta_struct(cfg, dtype))
    bsh = train_batch_shardings(cfg, mesh)
    batch_sh_fn = lambda tree: jax.tree.map(lambda s: bsh(len(s.shape)), tree)
    agent_sh = lambda tree: jax.tree.map(
        lambda s: NamedSharding(
            mesh, P(fa if fa else None, *([None] * (len(s.shape) - 1)))
        ),
        tree,
    )

    def specs_fn(shape: ShapeConfig, dt=dtype):
        sp = train_input_specs(cfg, shape, mesh, dt)
        sp["state"] = jax.eval_shape(
            lambda xx, yy: strategy.init_state(xx, yy, m), sp["x"], sp["y"]
        )
        agent_stack = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((m,) + s.shape, s.dtype), t
        )
        sp["tracker"] = (
            {"gx": agent_stack(sp["x"]), "gy": agent_stack(sp["y"])}
            if getattr(strategy, "use_correction", False)
            else {}
        )
        sp["weights"] = jax.ShapeDtypeStruct((m,), jnp.float32)
        sp["budgets"] = jax.ShapeDtypeStruct((m,), jnp.int32)
        sp["active"] = jax.ShapeDtypeStruct((m,), jnp.bool_)
        sp["prev_active"] = jax.ShapeDtypeStruct((m,), jnp.bool_)
        return sp

    def jitted(shape: ShapeConfig):
        sp = specs_fn(shape)
        st_sh = jax.tree.map(lambda _: replicated(mesh), sp["state"])
        rep = replicated(mesh)
        return jax.jit(
            rnd,
            in_shardings=(
                x_sh,
                y_sh,
                batch_sh_fn(sp["batch"]),
                st_sh,
                agent_sh(sp["tracker"]),
                rep,
                rep,
                rep,
                rep,
            ),
            out_shardings=(x_sh, y_sh, st_sh, agent_sh(sp["tracker"])),
            donate_argnums=(0,),
        )

    return jitted, specs_fn


def pod_aggregation_plan(cfg: ModelConfig, mesh, num_pods: int) -> Dict:
    """The two-level aggregation tree's placement on a launch mesh:
    agents (the fed-axes device product) are split into `num_pods`
    contiguous device groups (`mesh.pod_device_groups`), each owning
    the level-one partial weighted sum of its agents; only the per-pod
    partials cross group boundaries.  Returns the plan the dry-run
    records (`--pods`):

      num_pods / agents_per_pod / devices_per_pod — the tree shape;
      pod_payload_bytes — one pod's per-round wire price on the
      pod <-> server edge (dense packed framing, priced == measured —
      `fed.pods.pod_payload_bytes`);
      groups — per-pod device id lists.
    """
    from ..fed.pods import pod_payload_bytes
    from .mesh import pod_device_groups

    m = num_agents(mesh, cfg.fed_mode)
    groups = pod_device_groups(mesh, cfg.fed_mode, num_pods)
    x = abstract_params(cfg, jnp.bfloat16)
    y = delta_struct(cfg, jnp.bfloat16)
    return {
        "num_pods": num_pods,
        "agents_per_pod": m // num_pods,
        "devices_per_pod": len(groups[0]),
        "pod_payload_bytes": pod_payload_bytes(x, y, measured=False),
        "groups": [[d.id for d in g] for g in groups],
    }


def build_gather_decode_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    algorithm="fedgda_gt",
    dtype=jnp.bfloat16,
):
    """The async runtime's server-side exchange as one SPMD program on
    the production mesh: all-gather the per-agent packed correction
    payloads over the fed axes and decode them replicated.

    Returns (jitted, arg_structs, expected_gather_bytes) — compile and
    census the collectives; their all-gather bytes must track
    `transport.measured_bytes_per_round`'s payload share (the dry-run
    stores both, benchmarks/comm_collectives.py --check-async gates)."""
    from .multihost import build_gather_decode_step

    strategy = _resolve_cfg_strategy(cfg, algorithm)
    x = abstract_params(cfg, dtype)
    y = delta_struct(cfg, dtype)
    return build_gather_decode_step(
        strategy, x, y, mesh, fed_axes(mesh, cfg.fed_mode)
    )


# --------------------------------------------------------------------------
# serving (prefill builds the KV cache; decode extends it one token)
# --------------------------------------------------------------------------
def build_prefill_step(
    cfg: ModelConfig, mesh, *, dtype=jnp.bfloat16, sequence_parallel: bool = True,
    sharding_variant: str = "baseline",
):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    h_sh = (
        NamedSharding(mesh, P(dp if dp else None, "model", None))
        if sequence_parallel
        else None
    )

    def prefill(params, batch, caches):
        h = embed_inputs(params, cfg, batch)
        h, caches, _ = forward(params, cfg, h, caches=caches, h_sharding=h_sh)
        logits = logits_from_hidden(params, cfg, h[:, -1:])
        return logits, caches

    def encoder_fwd(params, batch):
        h = embed_inputs(params, cfg, batch)
        h, _, _ = forward(params, cfg, h, h_sharding=h_sh)
        return logits_from_hidden(params, cfg, h)

    def specs_fn(shape: ShapeConfig):
        sp = {
            "params": abstract_params(cfg, dtype),
            "batch": batch_struct(cfg, shape.global_batch, shape.seq_len, dtype),
        }
        if cfg.supports_decode:
            sp["caches"] = abstract_caches(
                cfg, shape.global_batch, shape.seq_len, dtype
            )
        return sp

    def jitted(shape: ShapeConfig):
        sp = specs_fn(shape)
        p_sh = param_shardings(sp["params"], cfg, mesh, sharding_variant)
        b_sh = jax.tree.map(
            lambda s: serve_batch_sharding(mesh, shape.global_batch, len(s.shape)),
            sp["batch"],
        )
        if not cfg.supports_decode:
            return jax.jit(encoder_fwd, in_shardings=(p_sh, b_sh))
        c_sh = cache_shardings(sp["caches"], cfg, mesh)
        return jax.jit(
            prefill,
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(serve_batch_sharding(mesh, shape.global_batch, 3), c_sh),
            donate_argnums=(2,),
        )

    return jitted, specs_fn


def build_decode_step(
    cfg: ModelConfig, mesh, *, dtype=jnp.bfloat16,
    sharding_variant: str = "baseline",
):
    """One new token against a seq_len KV cache (decode_32k / long_500k)."""

    def decode(params, caches, tokens, position):
        h = embed_inputs(params, cfg, {"tokens": tokens})
        h, caches, _ = forward(params, cfg, h, caches=caches, position=position)
        logits = logits_from_hidden(params, cfg, h)
        return logits, caches

    def specs_fn(shape: ShapeConfig):
        B = shape.global_batch
        return {
            "params": abstract_params(cfg, dtype),
            "caches": abstract_caches(cfg, B, shape.seq_len, dtype),
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "position": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def jitted(shape: ShapeConfig):
        sp = specs_fn(shape)
        B = shape.global_batch
        p_sh = param_shardings(sp["params"], cfg, mesh, sharding_variant)
        c_sh = cache_shardings(sp["caches"], cfg, mesh)
        t_sh = serve_batch_sharding(mesh, B, 2)
        return jax.jit(
            decode,
            in_shardings=(p_sh, c_sh, t_sh, replicated(mesh)),
            out_shardings=(serve_batch_sharding(mesh, B, 3), c_sh),
            donate_argnums=(1,),
        )

    return jitted, specs_fn


def step_builder_for(cfg: ModelConfig, shape: ShapeConfig, mesh, **kw):
    """Dispatch on the input-shape kind."""
    if shape.kind == "train":
        return build_train_step(cfg, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh)
    return build_decode_step(cfg, mesh)
