"""End-to-end federated minimax training driver.

Runs FedGDA-GT (or a baseline / scenario strategy — any
`resolve_strategy` name: local_sgda, sync_gda, partial_gt, compressed_gt,
quantized_gt, and the stochastic family sagda / local_sgda_plus with
`--noise` / `--momentum`) over one of the registry architectures, with
synthetic heterogeneous federated data, metrics and checkpointing.  The
sync runtime (the default) jits the whole round as one program on the
default device, with the parameters, `delta` and the strategy state
donated to it.  The round comes from the phase-split engine
(`make_round`), bitwise-identical to the legacy constructors for the
legacy names (tests/test_engine_parity); stateful strategies (sampling
RNG, error-feedback buffers) thread their state across rounds and into
checkpoints.

Two cuts size a registry model for one chip without changing any width:
`--num-layers N` keeps N layers (a whole number of periods of the layer
pattern) and `--vocab-size V` keeps the chip's share of the vocabulary
(the synthetic traffic draws its ids below V).  `--reduced` is the CPU
smoke preset, which does cut the widths.

`--runtime async` hands the same loss/strategy to
`fed.async_runtime.AsyncFederatedRunner`: per-agent-shard phase programs
on separate devices, server-side exchange, double-buffered broadcasts —
iterates match the sync loop to fp tolerance.  `init_distributed` runs
first either way, so a multi-process launch (JAX_COORDINATOR_ADDRESS set)
spans hosts transparently.

`--telemetry DIR` attaches the unified observability sink (`repro.obs`):
a structured run ledger (JSONL event stream + run manifest with the
resolved config, strategy signature, seed folds and schedule digest)
plus per-round spans, wire-byte counters, opt-in invariant probes
(`--telemetry-probes`) and sampled `jax.profiler` traces
(`--profile-rounds`).  Probes are evaluated by the runner paths
(`--population` and `--runtime async`); the raw fused sync loop emits
round spans + wire-byte counters only.  Without the flag nothing is
constructed and the runners execute their exact pre-telemetry traces.

`main(argv)` is also the programmatic entry point: it returns a
`TrainResult` with the final iterates and the logged losses.  The
persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR points,
else in `<repo>/.jax_cache` (`configure_compile_cache`).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \\
        --rounds 50 --local-steps 8 --agents 4 \\
        [--algorithm quantized_gt --quantization-bits 8] [--runtime async]
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \\
        --num-layers 1 --agents 2 --per-agent-batch 1 --seq-len 1024 \\
        --local-steps 2 --rounds 3          # one TPU v5e, published widths
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..checkpoint import save_checkpoint
from ..configs import ModelConfig, get_config
from ..core.engine import make_round
from ..data import federated_token_batches
from ..fed.strategies import resolve_strategy
from ..models import init_params, num_params
from ..problems.adversarial import (
    delta_projection,
    init_delta,
    make_adversarial_loss,
)

#: the fixed cache location when JAX_COMPILATION_CACHE_DIR is unset: the
#: path is part of the cache key, so it never depends on a temp name
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


class TrainResult(NamedTuple):
    """What `main` hands back to a caller driving it in-process."""

    params: Any
    delta: Any
    #: global loss after each logged round (`--log-every`, plus the last)
    losses: List[float]
    #: wall seconds per round; the fused sync loop ends each one with
    #: `block_until_ready`, the runners time their own rounds (metric
    #: evaluation included, the first round's compilation too)
    round_seconds: List[float]
    #: ahead-of-time compilation of the fused round (sync runtime only)
    compile_seconds: Optional[float]
    #: the resolved model config, per-agent loss and agent-stacked data,
    #: so a caller can evaluate the final iterate on the same problem
    cfg: ModelConfig
    loss_fn: Callable
    data: Any


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, before
    the first compile: JAX_COMPILATION_CACHE_DIR when set (JAX reads the
    variable itself, so nothing is set here), else `REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def build_fused_round(loss, strategy, local_steps: int, eta: float):
    """The jitted fused round the sync runtime runs:
    `(params, delta, data[, state]) -> (params, delta[, state])`, with
    the iterates and the strategy state donated so the round's outputs
    reuse their input buffers."""
    stateful = strategy.stateful
    return jax.jit(
        make_round(
            loss, strategy, local_steps, eta,
            proj_y=delta_projection(1.0), explicit_state=stateful,
        ),
        donate_argnums=(0, 1, 3) if stateful else (0, 1),
    )


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="depth cut: keep N layers, a whole number of "
                         "periods of the layer pattern (widths unchanged)")
    ap.add_argument("--vocab-size", type=int, default=None,
                    help="the chip's share of the vocabulary: ids, logits "
                         "and the loss are over the first V entries")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--per-agent-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--eta", type=float, default=2e-3)
    ap.add_argument("--heterogeneity", type=int, default=7)
    ap.add_argument("--algorithm", default="fedgda_gt",
                    help="any repro.fed.resolve_strategy name")
    ap.add_argument("--participation", type=float, default=None,
                    help="client fraction per round (partial_gt)")
    ap.add_argument("--compression-ratio", type=float, default=None,
                    help="kept fraction of sparsified corrections "
                         "(compressed_gt / quantized_gt)")
    ap.add_argument("--quantization-bits", type=int, default=None,
                    help="stochastic-quantization bit-width "
                         "(quantized_gt; >=32 disables)")
    ap.add_argument("--wire-transport", action="store_true",
                    help="move compressed corrections as packed "
                         "(value, index, scale) payloads "
                         "(compressed_gt / quantized_gt)")
    ap.add_argument("--noise", default=None,
                    choices=["gaussian", "minibatch"],
                    help="stochastic-gradient noise model (sagda / "
                         "local_sgda_plus and the noise-capable GT "
                         "aliases); unset = the deterministic oracle")
    ap.add_argument("--noise-sigma", type=float, default=None,
                    help="gaussian noise scale (default 0.1)")
    ap.add_argument("--noise-fraction", type=float, default=None,
                    help="minibatch subsampling fraction (default 0.5)")
    ap.add_argument("--noise-seed", type=int, default=None,
                    help="seed of the dedicated noise stream "
                         "(fed.noise.noise_key — a dedicated fold, "
                         "independent of sampling/compression RNG)")
    ap.add_argument("--momentum", type=float, default=None,
                    help="local heavy-ball momentum (local_sgda_plus)")
    ap.add_argument("--runtime", default="sync", choices=["sync", "async"],
                    help="sync: one fused round program per step; "
                         "async: per-agent-shard phase dispatch "
                         "(fed.async_runtime) across the local devices")
    from ..sim.scenarios import SCENARIOS

    ap.add_argument("--population", default=None,
                    choices=sorted(SCENARIOS),
                    help="client-population scenario (repro.sim): agents "
                         "join/leave/lag per a seeded RoundSchedule; the "
                         "runners execute the membership-aware elastic "
                         "round (stable = the legacy full-participation "
                         "path, bitwise)")
    ap.add_argument("--population-seed", type=int, default=0,
                    help="seed of the availability stream (a dedicated "
                         "fold — independent of model/data RNG)")
    ap.add_argument("--no-rebase", action="store_true",
                    help="ablation: naive membership handling (1/m "
                         "weights over the full registry, stale EF "
                         "residuals) — expected to stall under churn")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write a structured run ledger (events.jsonl + "
                         "manifest.json, repro.obs.RunLedger) under DIR "
                         "and emit per-round spans / wire-byte counters")
    ap.add_argument("--telemetry-probes", default="",
                    help="comma-separated invariant probes to sample "
                         "(repro.obs.probes: gt_residual, tracker_drift, "
                         "ef_residual, priced_vs_measured, duality_gap)")
    ap.add_argument("--telemetry-probe-every", type=int, default=1,
                    help="sample the enabled probes every N rounds")
    ap.add_argument("--profile-rounds", default="",
                    help="comma-separated round indices to wrap in a "
                         "jax.profiler trace (written under "
                         "DIR/profile; requires --telemetry)")
    return ap


def _resolve_config(ap: argparse.ArgumentParser, args) -> ModelConfig:
    """The registry config with the requested cuts applied; a cut that
    is not a share of the published model is a usage error."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cuts = {}
    if args.num_layers is not None:
        period = len(cfg.pattern)
        if args.num_layers < 1 or args.num_layers % period:
            ap.error(
                f"--num-layers {args.num_layers}: {cfg.name} needs a whole "
                f"number of its {period}-layer pattern periods "
                f"{cfg.pattern}"
            )
        cuts["num_layers"] = args.num_layers
    if args.vocab_size is not None:
        if not 1 <= args.vocab_size <= cfg.vocab_size:
            ap.error(
                f"--vocab-size {args.vocab_size}: must be a share of "
                f"{cfg.name}'s vocabulary of {cfg.vocab_size}"
            )
        cuts["vocab_size"] = args.vocab_size
    return dataclasses.replace(cfg, **cuts) if cuts else cfg


def _describe(cfg: ModelConfig, published: ModelConfig, params) -> str:
    return (
        f"arch={cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
        f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} num_layers={cfg.num_layers}/{published.num_layers} "
        f"vocab={cfg.vocab_size}/{published.vocab_size} "
        f"params={num_params(params)/1e6:.1f}M"
    )


def main(argv: Optional[List[str]] = None) -> TrainResult:
    ap = _parser()
    args = ap.parse_args(argv)
    configure_compile_cache()

    from .multihost import init_distributed

    init_distributed()  # no-op unless a multi-process launch is configured

    # resolve the strategy first: a bad --algorithm must fail before the
    # expensive model/data setup below.  Only pass knobs the user set —
    # unset flags must not override the registry defaults (e.g.
    # compressed_gt's active 0.1 ratio)
    knobs = {
        "participation": args.participation,
        "compression_ratio": args.compression_ratio,
        "quantization_bits": args.quantization_bits,
        "wire_transport": args.wire_transport or None,
        "noise": args.noise,
        "noise_sigma": args.noise_sigma,
        "noise_fraction": args.noise_fraction,
        "noise_seed": args.noise_seed,
        "momentum": args.momentum,
    }
    strategy = resolve_strategy(
        args.algorithm, **{k: v for k, v in knobs.items() if v is not None}
    )

    cfg = _resolve_config(ap, args)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg, jnp.float32)
    delta = init_delta(cfg)
    print(_describe(cfg, get_config(args.arch), params))
    print(f"agents={args.agents} per_agent_batch={args.per_agent_batch} "
          f"seq_len={args.seq_len} K={args.local_steps} "
          f"algo={args.algorithm} runtime={args.runtime}")

    data = federated_token_batches(
        jax.random.PRNGKey(1), args.agents, args.per_agent_batch,
        args.seq_len, cfg.vocab_size, heterogeneity=args.heterogeneity,
    )
    loss = make_adversarial_loss(cfg, remat=False)

    def global_loss(x, y):
        per = jax.vmap(loss, in_axes=(None, None, 0))(x, y, data)
        return jnp.mean(per)

    gl = jax.jit(global_loss)

    def logged(t: int) -> bool:
        return t % args.log_every == 0 or t == args.rounds - 1

    def runner_result(runner, params, delta) -> TrainResult:
        logged_stats = [h for h in runner.history if logged(h.round_index)]
        return TrainResult(
            params, delta, [h.metrics["loss"] for h in logged_stats],
            [h.seconds for h in runner.history], None, cfg, loss, data,
        )

    schedule = None
    rebase = not args.no_rebase
    if args.population:
        from ..sim import make_population

        pop = make_population(args.population, args.agents)
        schedule = pop.schedule(
            args.population_seed, args.rounds, args.local_steps
        )
        print(
            f"population={args.population} seed={args.population_seed} "
            f"participation={schedule.participation_rate():.2f} "
            f"churn_events={schedule.churn_events()} rebase={rebase}"
        )

    telemetry = ledger = None
    if args.telemetry:
        from ..obs import RunLedger, Telemetry, run_manifest

        ledger = RunLedger(args.telemetry)
        probes = tuple(p for p in args.telemetry_probes.split(",") if p)
        prof = tuple(int(r) for r in args.profile_rounds.split(",") if r)
        telemetry = Telemetry(
            ledger=ledger, probes=probes,
            probe_every=args.telemetry_probe_every,
            profile_dir=(os.path.join(args.telemetry, "profile")
                         if prof else None),
            profile_rounds=prof,
        )
        ledger.write_manifest(run_manifest(
            config=vars(args), strategy=strategy,
            noise_seed=args.noise_seed,
            availability_seed=(args.population_seed if args.population
                               else None),
            schedule=schedule,
        ))
        print(f"telemetry: ledger at {args.telemetry}")

    if args.runtime == "async":
        from ..fed import AsyncFederatedRunner

        runner = AsyncFederatedRunner(
            loss, strategy, data, args.local_steps, args.eta,
            proj_y=delta_projection(1.0),
            metric_fn=lambda x, y: {
                "loss": global_loss(x, y),
                "delta_norm": jnp.linalg.norm(y["delta"]),
            },
            telemetry=telemetry,
        )
        params, delta = runner.run(
            params, delta, args.rounds, log_every=args.log_every,
            schedule=schedule, rebase=rebase,
        )
        if args.ckpt_dir:
            save_checkpoint(
                args.ckpt_dir, args.rounds, {"x": params, "y": delta}
            )
        if telemetry is not None:
            telemetry.close()
        print("done.")
        return runner_result(runner, params, delta)

    if schedule is not None:
        # elastic sync run: the runner owns the schedule threading
        # (membership-aware round, tracker table, rebase hook)
        from ..fed import FederatedRunner

        runner = FederatedRunner.from_strategy(
            loss, strategy, data, args.local_steps, args.eta,
            proj_y=delta_projection(1.0),
            metric_fn=lambda x, y: {
                "loss": global_loss(x, y),
                "delta_norm": jnp.linalg.norm(y["delta"]),
            },
            checkpoint_dir=args.ckpt_dir,
            checkpoint_every=50 if args.ckpt_dir else 0,
            telemetry=telemetry,
        )
        params, delta = runner.run(
            params, delta, args.rounds, log_every=args.log_every,
            schedule=schedule, rebase=rebase,
        )
        if telemetry is not None:
            telemetry.close()
        print("done.")
        return runner_result(runner, params, delta)

    stateful = strategy.stateful
    state = strategy.init_state(params, delta, args.agents) if stateful else None
    per_agent = None
    if telemetry is not None:
        from ..fed.transport import measured_bytes_per_round

        per_agent = int(measured_bytes_per_round(
            strategy, params, delta, args.local_steps
        ))
    carry = (params, delta, state) if stateful else (params, delta)
    c0 = time.perf_counter()
    rnd = build_fused_round(loss, strategy, args.local_steps, args.eta)
    rnd = rnd.lower(*carry[:2], data, *carry[2:]).compile()
    compile_seconds = time.perf_counter() - c0
    print(f"compiled the fused round in {compile_seconds:.2f}s", flush=True)
    losses: List[float] = []
    round_seconds: List[float] = []
    t0 = time.time()
    for t in range(args.rounds):
        rt0 = time.perf_counter()
        if telemetry is not None:
            telemetry.begin_round(t)
        if stateful:
            params, delta, state = rnd(params, delta, data, state)
        else:
            params, delta = rnd(params, delta, data)
        jax.block_until_ready(params)
        round_seconds.append(time.perf_counter() - rt0)
        if telemetry is not None:
            telemetry.round_event(
                t, runtime="fused", seconds=round_seconds[-1]
            )
            telemetry.counter(
                "wire_bytes", per_agent * args.agents,
                per_agent=per_agent, n_active=args.agents,
            )
            telemetry.end_round(t)
        if logged(t):
            lv = float(gl(params, delta))
            losses.append(lv)
            dn = float(jnp.linalg.norm(delta["delta"]))
            print(f"[round {t:4d}] loss={lv:.4f} |delta|={dn:.4f} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        if args.ckpt_dir and (t + 1) % 50 == 0:
            payload = {"x": params, "y": delta}
            if state is not None:
                # resuming without this replays RNG draws / zeroes the
                # error-feedback buffers
                payload["strategy_state"] = state
            save_checkpoint(args.ckpt_dir, t + 1, payload)
    if telemetry is not None:
        telemetry.close()
    print("done.")
    return TrainResult(
        params, delta, losses, round_seconds, compile_seconds, cfg, loss,
        data,
    )


if __name__ == "__main__":
    main()
