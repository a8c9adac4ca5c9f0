"""Run one cell of the chip benchmark once and print its result.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from `BENCHMARK.json` and the files it names, needs a
TPU with as many chips as the cell asks for (it exits non-zero, and
prints no result, on any other platform), and prints as its last line
one JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics),
`device`, with `--trace 1` `breakdown`, and last `checks`, each compared
number beside its limit.  The same numbers end its standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, registry

    cell = registry.cell(registry.load_benchmark(), args.workload)
    devices = harness.require_devices(cell["workload"]["chips"])
    harness.configure_jax()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices, T_START)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
