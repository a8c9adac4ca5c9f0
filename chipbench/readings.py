"""Readings that set a cell's correctness limits, on the chip at the
cell's own size (the benchmark's own runs never run this).

    python chipbench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]

For every seed it drives the program through the cell's check rounds as
a run does and compares with the reference (`compare.readings`): the
lower readings.  For the control seeds it does the same with the
program's own bfloat16 path switched on (parameters, `delta` and updates
in bfloat16, the precision below the configuration's float32), and for
the fault seeds with each of the cell's planted faults (`faults.py`):
the upper readings.  One JSON line per reading, then a summary: the
largest lower reading and the smallest upper reading of each number.
Each line also carries every leaf's change norms on both sides, so that
another number can be worked out from the same runs.
After the first sound program's check rounds it also prints the
compiled round's `memory_analysis()` beside the device's
`memory_stats()`, the cross-check of `hbm_peak_gb`'s keys.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def _memory(prog, device):
    ma = prog.rnd.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {"memory_analysis": {k: getattr(ma, k, None) for k in keys},
            "memory_stats": device.memory_stats()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from chipbench import compare, faults, harness, registry

    cell = registry.cell(registry.load_benchmark(), args.workload)
    devices = harness.require_devices(cell["workload"]["chips"])
    harness.configure_jax()
    c, t = cell["config"], cell["traffic"]
    n = t["check_rounds"]
    runs = [(s, "program") for s in args.seeds]
    runs += [(s, "control") for s in args.control_seeds]
    # a state left unchanged reads 1 by the measure and needs no run
    runs += [(s, f) for s in args.fault_seeds for f in faults.FAULTS if f != "unchanged"]
    by_kind = {}
    memory_read = False
    for seed in sorted({s for s, _ in runs}):
        progs = {}
        for s, kind in runs:
            if s != seed:
                continue
            t0 = time.perf_counter()
            dtype = jnp.bfloat16 if kind == "control" else jnp.float32
            plant = faults.FAULTS[kind]() if kind in faults.FAULTS else contextlib.nullcontext()
            try:
                with plant:
                    prog = harness.Program(c, t, seed, dtype, devices)
                    progs[kind] = harness.check_rounds(prog, n)
                    if kind == "program" and not memory_read:
                        print(json.dumps(_memory(prog, devices[0])), flush=True)
                        memory_read = True
                    del prog
            except Exception as e:  # a control that crashes has failed
                progs[kind] = None
                print(f"{kind} seed {seed} failed: {e!r}", file=sys.stderr)
            gc.collect()
            print(f"{kind} seed {seed}: {time.perf_counter() - t0:.1f} s",
                  file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        ref = harness.reference_readings(c, t, seed, n)
        print(f"reference seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        for kind, p in progs.items():
            r = compare.readings(p, ref) if p is not None else {}
            line = {"seed": seed, "kind": kind,
                    **{k: v["value"] for k, v in r.items()},
                    "leaves": {k: v.get("leaf") for k, v in r.items()},
                    "norms": {"program": p, "reference": ref}}
            print(json.dumps(line), flush=True)
            for k, v in r.items():
                by_kind.setdefault(kind, {}).setdefault(k, []).append(v["value"])
    summary = {kind: {k: (max(v) if kind == "program" else min(v)) for k, v in d.items()}
               for kind, d in by_kind.items()}
    print(json.dumps({"summary": summary, "limits": cell["limits"],
                      "seconds": time.perf_counter() - T_START}))


if __name__ == "__main__":
    main()
