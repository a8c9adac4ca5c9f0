"""The telemetry sink: per-round / per-phase spans, counters, probes.

One owner for observability events across every runtime.  A `Telemetry`
instance is a host-side event sink the runners emit into — it never
appears inside a jitted program, so a runner given `telemetry=None`
executes the EXACT pre-telemetry trace (the bitwise pin shares the
noise/momentum elision discipline: disabled means absent, not zeroed;
tests/test_obs.py).  Enabled without probes it costs a few microseconds
of host bookkeeping per round (`benchmarks/obs.py` gates the wall-clock
overhead at <= 3%).

Event kinds (each event is one flat dict; the schema is the
observability contract in tests/README.md):

  span     {"kind": "span", "name": <phase or "round">, "round": t,
            "seconds": wall, ...}  — "round" spans carry the runtime
            ("sync" / "async" / "multihost" / "sparse" / elastic
            labels) and dispatch counts; phase spans are named after
            `core.engine.make_phases` (broadcast /
            exchange_corrections / local_steps / aggregate).  On the
            async runtimes a phase span measures dispatch + host time
            (jax's async dispatch returns before the device finishes).
            Device time per phase is in a profiler trace: the engine
            names each phase inside the compiled program
            (`jax.named_scope`), and `chipbench/scopes.py` reads it.
  counter  {"kind": "counter", "name": ..., "round": t, "value": n, ...}
           — wire bytes ("wire_bytes" with per_agent / n_active,
           "gathered_payload_bytes" on the multihost gather), peak
           memory, active-set sizes.
  probe    {"kind": "probe", "name": ..., "round": t, "value": ...} —
           sampled invariant probes (`repro.obs.probes`): opt in by
           name via `probes=(...)`, sampled every `probe_every` rounds.
  event    {"kind": "event", "name": ..., ...} — discrete occurrences:
           "shard_skipped" (async elastic), "realign" / "dense_fallback"
           (sparse engine).

The `round` field defaults to the sink's `current_round`, set by
`begin_round` — emitters deep inside a runner (a skipped shard, a wire
gather) need no round plumbing.  Attach a `repro.obs.RunLedger` to
stream every event to JSONL as it is emitted; `profile_rounds` wraps the
listed rounds in a `jax.profiler` trace (written under `profile_dir`;
consecutive rounds share one trace).  Spans and rounds are also on the
profiler's clock: a span enters `jax.profiler.TraceAnnotation(name)`,
and `begin_round` / `end_round` hold a
`StepTraceAnnotation("round", step_num=t)`, so a trace shows the
program's own spans beside the device's operations
(`chipbench/trace.py load` reads them as host spans).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional, Sequence


class Telemetry:
    """Host-side observability sink (see module docstring).

    Off is `None`, not a disabled instance: runners guard every emit
    site with `if telemetry is not None`, so the disabled path is the
    pre-telemetry code verbatim.
    """

    def __init__(
        self,
        ledger=None,
        probes: Sequence[str] = (),
        probe_every: int = 1,
        gap_fn: Optional[Callable] = None,
        profile_dir: Optional[str] = None,
        profile_rounds: Sequence[int] = (),
    ):
        self.events: List[Dict[str, Any]] = []
        self.ledger = ledger
        self.probes = frozenset(probes)
        self.probe_every = max(1, int(probe_every))
        #: duality-gap oracle for the "duality_gap" probe — supplied by
        #: the caller (the saddle point is problem knowledge, not ours)
        self.gap_fn = gap_fn
        self.profile_dir = profile_dir
        self.profile_rounds = frozenset(int(r) for r in profile_rounds)
        self.current_round: Optional[int] = None
        self._profiling = False
        self._round_annotation = None

    # ------------------------------------------------------------- emit
    def emit(self, kind: str, name: str, round: Optional[int] = None,
             **attrs) -> Dict[str, Any]:
        ev: Dict[str, Any] = {"kind": kind, "name": name}
        r = self.current_round if round is None else round
        if r is not None:
            ev["round"] = int(r)
        ev.update(attrs)
        self.events.append(ev)
        if self.ledger is not None:
            self.ledger.write(ev)
        return ev

    def counter(self, name: str, value, round: Optional[int] = None,
                **attrs) -> Dict[str, Any]:
        return self.emit("counter", name, round=round, value=int(value),
                         **attrs)

    @contextlib.contextmanager
    def span(self, name: str, round: Optional[int] = None, **attrs):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.emit("span", name, round=round,
                      seconds=time.perf_counter() - t0, **attrs)

    def round_event(self, t: int, runtime: str, seconds: float,
                    **attrs) -> Dict[str, Any]:
        """The per-round span, emitted post-hoc from the runner's own
        wall-clock measurement (the same number its history records)."""
        return self.emit("span", "round", round=t, seconds=float(seconds),
                         runtime=runtime, **attrs)

    # ------------------------------------------------------------ rounds
    def begin_round(self, t: int) -> None:
        import jax

        self.current_round = int(t)
        if self.profile_dir is not None and t in self.profile_rounds:
            self.start_profile()
        self._close_round()
        self._round_annotation = jax.profiler.StepTraceAnnotation(
            "round", step_num=int(t))
        self._round_annotation.__enter__()

    def end_round(self, t: int) -> None:
        self._close_round()
        if self._profiling and t + 1 not in self.profile_rounds:
            self.stop_profile()

    def _close_round(self) -> None:
        if self._round_annotation is not None:
            self._round_annotation.__exit__(None, None, None)
            self._round_annotation = None

    def close(self) -> None:
        """Stop a profile that the last round left open (its next round
        was listed too) and close the ledger."""
        self._close_round()
        self.stop_profile()
        if self.ledger is not None:
            self.ledger.close()

    def start_profile(self) -> None:
        if self._profiling:
            return
        import jax

        jax.profiler.start_trace(self.profile_dir)
        self._profiling = True

    def stop_profile(self) -> None:
        if not self._profiling:
            return
        import jax

        jax.profiler.stop_trace()
        self._profiling = False
        self.emit("event", "profile_trace", dir=self.profile_dir)

    # ------------------------------------------------------------ probes
    def probe_due(self, name: str, t: int) -> bool:
        return name in self.probes and t % self.probe_every == 0

    def probe_value(self, name: str, t: int, value, **attrs) -> Dict:
        return self.emit("probe", name, round=t, value=value, **attrs)

    # ----------------------------------------------------------- queries
    def series(self, kind: Optional[str] = None,
               name: Optional[str] = None) -> List[Dict[str, Any]]:
        return [
            e for e in self.events
            if (kind is None or e["kind"] == kind)
            and (name is None or e["name"] == name)
        ]

    def probe_series(self, name: str) -> List[Any]:
        return [e["value"] for e in self.series("probe", name)]


def maybe_span(telemetry: Optional[Telemetry], name: str, **attrs):
    """`telemetry.span(...)` when enabled, a no-op context otherwise —
    lets runner phase blocks stay un-duplicated across the two modes
    (the disabled branch is a bare `nullcontext`, zero JAX-graph
    change)."""
    if telemetry is None:
        return contextlib.nullcontext()
    return telemetry.span(name, **attrs)
