"""Drive one cell: build the program under test from the cell's files,
take it through the rounds that the correctness check reads, time a
closed loop of rounds for the window, read device memory and (with
`trace`) a profiler trace, then free the program and compare with the
plain reference.

The program is what `repro.launch.train` runs: the fused round of the
sync runtime, `train.build_fused_round` compiled ahead of time and
called once per round, each round ending in `block_until_ready`.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from . import compare, flops, peaks, reference, registry, scopes
from . import trace as trace_mod

ROOT = registry.ROOT
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: files whose Python frames name the device's idle gaps in a trace
HOST_FILES = tuple(sorted(
    ({p.name for p in (ROOT / "src" / "repro").rglob("*.py")}
     | {p.name for p in registry.HERE.glob("*.py")}) - {"__init__.py"}))


def configure_jax() -> None:
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_devices(chips: int) -> List:
    """The first `chips` TPU devices; exits non-zero off a TPU or with
    fewer chips than the cell needs."""
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform "
                         f"{platform!r} with {len(devs)} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX "
                         f"found {len(devs)} {devs[0].device_kind}")
    peaks.peak(devs[0].device_kind)  # an unknown chip is an error
    return devs[:chips]


def memory_peak_bytes(stats: Optional[Dict]) -> Optional[int]:
    """Peak device memory of a run: live arrays plus program scratch.
    `peak_bytes_in_use` counts the live arrays; the scratch of a
    running program is reserved apart and shows in
    `peak_bytes_reserved`."""
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return int(stats["peak_bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0))


# ------------------------------------------------------------ the program
def program_config(c: Dict):
    """The program's model config for configuration file `c`, cut as the
    file says, parsed and resolved as `train.main` does it from the
    arguments that the file's architecture module gives; a field that
    differs from the file is an error."""
    from repro.launch import train

    arch = registry.arch(c["arch"])
    ap = train._parser()
    cfg = train._resolve_config(ap, ap.parse_args(arch.program_args(c)))
    want = arch.program_fields(c)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} differs from the file's {want}")
    return cfg


class Program:
    """The fused round of the sync runtime, compiled ahead of time, with
    its inputs made from the seed on the device in one jitted call each
    by the program's own helpers."""

    def __init__(self, c: Dict, t: Dict, seed: int, dtype, devices):
        from repro.data import federated_token_batches
        from repro.fed.strategies import resolve_strategy
        from repro.launch import train
        from repro.models import init_params
        from repro.problems.adversarial import init_delta, make_adversarial_loss

        self.cfg = cfg = program_config(c)
        self.t, self.devices = t, devices
        self.pkey, dkey = reference.seed_keys(seed)
        self.init = jax.jit(lambda k: init_params(k, cfg, dtype))
        self.delta0 = lambda: init_delta(cfg, dtype)
        m, B, S = t["agents"], t["per_agent_batch"], t["seq_len"]
        self.data = jax.jit(lambda k: federated_token_batches(
            k, m, B, S, cfg.vocab_size, heterogeneity=t["heterogeneity"]))(dkey)
        strategy = resolve_strategy(t["algorithm"], **t["strategy"])
        loss = make_adversarial_loss(cfg, remat=False)

        x = self.init(self.pkey)
        y = self.delta0()
        self.carry = (x, y) + ((strategy.init_state(x, y, m),)
                               if strategy.stateful else ())
        rnd = train.build_fused_round(loss, strategy, t["local_steps"], t["eta"])
        c = self.carry
        self.rnd = rnd.lower(c[0], c[1], self.data, *c[2:]).compile()

    def change_norms(self) -> Dict[str, float]:
        x, y = self.carry[0], self.carry[1]
        return compare.change_norms(x, y, self.init(self.pkey), self.delta0())

    def rounds(self, n: int) -> None:
        for _ in range(n):
            c = self.carry
            self.carry = self.rnd(c[0], c[1], self.data, *c[2:])
            jax.block_until_ready(self.carry)

    def window(self, seconds: float) -> (List[float], float):
        times: List[float] = []
        w0 = time.perf_counter()
        while True:
            r0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("round"):
                c = self.carry
                with jax.profiler.TraceAnnotation("dispatch"):
                    out = self.rnd(c[0], c[1], self.data, *c[2:])
                with jax.profiler.TraceAnnotation("block"):
                    jax.block_until_ready(out)
                self.carry = out
            end = time.perf_counter()
            times.append(end - r0)
            if end - w0 >= seconds:
                return times, end - w0

    def traced(self, n: int) -> None:
        for _ in range(n):
            self.window(0.0)  # one round


# ---------------------------------------------------------------- a run
def reference_readings(c: Dict, t: Dict, seed: int, rounds: int) -> Dict:
    """The reference's leaf norms after round 1 and after `rounds`."""
    ref = reference.FedGDAGT(c, t["local_steps"], t["eta"])
    pkey, dkey = reference.seed_keys(seed)
    x0 = jax.jit(lambda k: reference.init_params(k, c))(pkey)
    y0 = {"delta": jnp.zeros((c["hidden_size"],), jnp.float32)}
    data = jax.jit(lambda k: reference.make_data(
        k, t["agents"], t["per_agent_batch"], t["seq_len"], c["vocab_size"],
        t["heterogeneity"]))(dkey)
    out: Dict = {}
    for i, (x, y) in enumerate(ref.rounds(x0, y0, data, rounds), 1):
        if i == 1:
            out["update1"] = compare.change_norms(x, y, x0, y0)
    out["change3"] = compare.change_norms(x, y, x0, y0)
    return out


def check_rounds(prog: Program, n: int) -> Dict:
    """Drive the program through its first n rounds from the seed and
    record what the comparison reads."""
    prog.rounds(1)
    read = {"update1": prog.change_norms()}
    prog.rounds(n - 1)
    read["change3"] = prog.change_norms()
    return read


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float, dtype=jnp.float32,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)) -> Dict:
    c, t = cell["config"], cell["traffic"]
    n_check = t["check_rounds"]
    prog = Program(c, t, seed, dtype, devices)
    read_prog = check_rounds(prog, n_check)
    setup_s = time.perf_counter() - t_start
    times, window_s = prog.window(seconds)
    log(f"window: {len(times)} rounds in {window_s:.3f} s, set-up {setup_s:.3f} s")
    summary = None
    if trace:
        summary = _trace(prog, t["trace_rounds"])
    stats = [d.memory_stats() for d in devices]
    del prog
    gc.collect()

    read_ref = reference_readings(c, t, seed, n_check)
    read = compare.readings(read_prog, read_ref)
    correct, checks = compare.judge(read, cell["limits"])

    dev = devices[0]
    ctx = {
        "times": times, "window_s": window_s, "rounds": len(times),
        "setup_s": setup_s, "memory": stats,
        "memory_peak": [memory_peak_bytes(s) for s in stats],
        "flops_per_round": flops.round_flops(c, t),
        "chips": len(devices), "device_kind": dev.device_kind,
        # None off a TPU (the CPU tests); `require_devices` refuses an
        # unknown chip before a run starts
        "peak": peaks.PEAKS.get(dev.device_kind), "trace": summary,
        "config": c, "traffic": t,
    }
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry, reader in cell[kind]:
        v = reader.read(ctx)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    peaks_b = [p for p in ctx["memory_peak"] if p is not None]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peaks_b) if peaks_b else None}
    result = {"correct": correct, "attempted": n_check + len(times),
              "failed": 0 if correct else n_check, "metrics": metrics,
              "device": device}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    for name, ch in checks.items():
        leaf = read.get(name, {}).get("leaf")
        log(f"check {name}: {ch['value']!r} (limit {ch['limit']!r})"
            + (f" worst leaf {leaf}" if leaf else ""))
    return result


def _trace(prog: Program, n: int) -> Dict:
    """`trace.reduce`'s summary of n profiled rounds, with under
    `scopes` the split of `scopes.reduce` by the compiled round's
    scope names and by kernel."""
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            prog.traced(n)
        finally:
            jax.profiler.stop_trace()
        events = trace_mod.load(trace_mod.find_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    summary = trace_mod.reduce(events, host_files=HOST_FILES)
    summary["scopes"] = scopes.reduce(events, scopes.op_scopes(prog.rnd.as_text()))
    return summary
