"""Quickstart: the paper in 60 seconds.

Solves the Section-5.1 federated quadratic minimax game with one round
engine and six communication strategies — centralized GDA (FullSync),
Local SGDA (LocalOnly), FedGDA-GT (GradientTracking, this paper), plus
the scenario-opening variants: client sampling (PartialParticipation),
sparsified corrections with error feedback (CompressedGT), and QSGD-style
stochastically quantized corrections (QuantizedGT) — and prints the
optimality gap every few hundred rounds.  FedGDA-GT is the only one that
is simultaneously accurate (exact limit) and cheap (K local steps per
communication round).

Compression knobs (CompressedGT / QuantizedGT):
  compression_ratio / ratio  kept fraction of correction entries per
                             round (1.0 = dense); `mode` picks "topk"
                             (largest magnitude) or "randk" (uniform)
  bits                       QuantizedGT only: stochastic-quantization
                             bit-width for the kept entries, per-agent
                             max-abs scale, unbiased rounding (>= 32
                             disables; bits=32 + ratio=1.0 IS FedGDA-GT)
  error_feedback             accumulate what compression dropped and
                             re-inject it next round (tightens the floor)
  use_kernel                 dispatch lane-aligned leaves to the fused
                             Pallas compress-correction kernel
                             (kernels/compress_correction.py): compiled
                             on a TPU, interpreted on the CPU; a TPU
                             refuses the exact-k selection (ratio < 1)

Two finales: FedGDA-GT once more on the ASYNC runtime
(`fed.async_runtime.AsyncFederatedRunner`): the same four round phases
(broadcast / exchange_corrections / local_steps / aggregate — see
`repro.core.engine.make_phases`) dispatched per agent shard on separate
emulated devices, with the exchange server-side and broadcasts
double-buffered — same answer to fp tolerance, overlapped schedule.
Then an ELASTIC run (`repro.sim`): the same game under a flaky Markov
join/leave population, where FedGDA-GT with membership-aware tracker
rebasing still converges to the exact minimax point while Local SGDA
under the identical churn stalls at its bias floor.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

# 8 emulated host devices so the async finale has shards to land on
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core import make_round, run_strategy_rounds, tree_sq_dist
from repro.fed import (
    CompressedGT,
    FullSync,
    GradientTracking,
    LocalOnly,
    PartialParticipation,
    QuantizedGT,
)
from repro.problems import make_quadratic_problem, quadratic_minimax_point


def main() -> None:
    # 20 heterogeneous agents, d = 50 (the paper's own setup)
    prob = make_quadratic_problem(
        jax.random.PRNGKey(0), dim=50, num_samples=500, num_agents=20
    )
    x_star, y_star = quadratic_minimax_point(prob)

    def gap(x, y):
        return {"gap": tree_sq_dist(x, x_star) + tree_sq_dist(y, y_star)}

    K, eta, T = 20, 1e-4, 2000
    strategies = {
        "centralized GDA   (communicates every step)": (FullSync(), 1),
        "Local SGDA  K=20  (biased fixed point)": (LocalOnly(), K),
        "FedGDA-GT   K=20  (this paper)": (GradientTracking(), K),
        "FedGDA-GT   K=20  50% client sampling": (
            PartialParticipation(participation=0.5, seed=0), K,
        ),
        # wire_transport: the corrections are really encoded as packed
        # (value, index, scale) payloads and decoded server-side — same
        # iterates bit for bit, payload bytes matching bytes_per_round
        "FedGDA-GT   K=20  top-10% corrections + error feedback": (
            CompressedGT(
                compression_ratio=0.1, mode="topk", wire_transport=True
            ), K,
        ),
        "FedGDA-GT   K=20  8-bit quantized corrections (unbiased + EF)": (
            QuantizedGT(bits=8, seed=0, wire_transport=True), K,
        ),
    }
    x0 = jnp.zeros(50)
    print(f"rounds={T}  local steps K={K}  eta={eta}\n")
    m = jax.tree.leaves(prob.agent_data)[0].shape[0]
    for name, (strategy, k) in strategies.items():
        # explicit_state works for stateless strategies too (state is {}),
        # so one code path serves all five
        rnd = make_round(prob.loss, strategy, k, eta, explicit_state=True)
        state0 = strategy.init_state(x0, x0, m)
        (_, _, _), mtr = run_strategy_rounds(
            jax.jit(rnd), x0, x0, prob.agent_data, T, state0, gap
        )
        g = mtr["gap"]
        marks = "  ".join(
            f"t={t}: {float(g[t]):.1e}" for t in (0, 100, 500, 1000, T - 1)
        )
        print(f"{name}\n  {marks}\n")

    # the async runtime: same phases, per-agent-shard dispatch
    from repro.fed import AsyncFederatedRunner, FederatedRunner

    runner = AsyncFederatedRunner(
        prob.loss, GradientTracking(), prob.agent_data, K, eta,
        metric_fn=gap,
    )
    xa, ya = runner.run(x0, x0, 500)
    print(
        f"FedGDA-GT on the async runtime ({runner._n_shards} agent shards"
        f" over {len(jax.devices())} devices)\n"
        f"  t=500: {runner.metric_series('gap')[-1]:.1e}"
        " (matches the sync runner to fp tolerance)\n"
    )

    # the elastic finale: a FLAKY population (repro.sim) — agents join
    # and leave between rounds per a seeded Markov churn process.  The
    # membership-aware elastic round re-normalizes the server weights
    # over each round's active set and keeps a per-agent tracker table
    # (absent agents stand in with their last anchor gradient; rejoining
    # agents re-anchor at the current iterate within one round), so
    # FedGDA-GT KEEPS its exact limit under churn; Local SGDA under the
    # very same churn stays pinned at its bias floor.
    from repro.sim import make_population

    schedule = make_population("flaky", m).schedule(0, T, K)
    print(
        f"flaky population: {schedule.participation_rate():.0%} mean "
        f"participation, {schedule.churn_events()} churn events in {T} rounds"
    )
    for name, strategy in (
        ("FedGDA-GT   K=20  + tracker rebase", GradientTracking()),
        ("Local SGDA  K=20  (same churn)", LocalOnly()),
    ):
        er = FederatedRunner.from_strategy(
            prob.loss, strategy, prob.agent_data, K, eta, metric_fn=gap
        )
        er.run(x0, x0, T, schedule=schedule)
        g = er.metric_series("gap")
        marks = "  ".join(
            f"t={t}: {float(g[t]):.1e}" for t in (0, 100, 500, 1000, T - 1)
        )
        print(f"{name}\n  {marks}\n")

    print("FedGDA-GT converges linearly to the EXACT minimax point with a")
    print("constant stepsize — even under join/leave churn, thanks to the")
    print("membership-aware tracker rebase; Local SGDA plateaus at its bias")
    print("floor; client sampling and compressed corrections trade a small")
    print("accuracy floor for less communication (the unbiased 8-bit")
    print("quantizer's floor is the tightest); centralized GDA matches")
    print("FedGDA-GT's limit but needs K x more communication rounds")
    print("(Theorem 1).")


if __name__ == "__main__":
    main()
