"""Find a cell's files by the names in `BENCHMARK.json`.

- configuration `<c>`: the `file` of its entry, `chipbench/configs/<c>.json`;
- traffic mix `<t>`: `chipbench/traffic/<t>.json`;
- correctness limits of cell `<w>`: `chipbench/limits/<w>.json`;
- metric `<m>`: module `chipbench/metrics/<m>.py`, whose `read(ctx)`
  returns the number or None where the run has nothing to read; a cell
  reports the metrics whose readers find something in it, of those
  whose entry lists the cell under `workloads` or has no such list.
  `ctx` (`harness.run_cell`) holds the run's times, memory and device,
  the cell's `config` and `traffic`, and with a trace `trace`:
  `trace.reduce`'s summary, whose `scopes` is `scopes.reduce`'s split
  (`scope_s`, device seconds per round under each scope name the
  program gives, and `kernel_s`, by kernel name).  A reader of a new
  scope or kernel needs only the name; one of a kernel's roofline takes
  its bytes or FLOPs from the architecture module and `peaks.share`.
  An optional `EXAMPLE = (ctx, value)` in the module is a filled run
  and what it reads there, for the tests;
- architecture `<a>`, the `arch` key of a configuration file: module
  `chipbench/archs/<a>.py`, everything of the benchmark that knows the
  model.  It gives `program_args(c)`, the `launch/train.py` arguments
  that cut the program to the file; `program_fields(c)`, the program's
  `ModelConfig` fields as the file states them; `init_params(key, c,
  dtype)` and `agent_loss(params, delta, batch, c)`, the plain float32
  reference model; `forward_flops(c, batch, seq, *, causal_half)`,
  its forward FLOPs split into `matmul` and `elementwise`; and, where
  the model runs kernels of its own, `kernel_bytes(c, traffic)` or
  `kernel_flops(c, traffic)`, per round by kernel name.
  `chipbench/archs/common.py` holds the parts they share.

A later cell, configuration, architecture or metric is a new file and a
new entry in `BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
ARCHS = HERE / "archs"


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(kind: str, name: str) -> Dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def config(entry: Dict) -> Dict:
    return json.loads((ROOT / entry["file"]).read_text())


def traffic(name: str) -> Dict:
    return _json("traffic", name)


def limits(workload: str) -> Dict[str, float]:
    return _json("limits", workload)


def _module(path: pathlib.Path, what: str, module_name: str):
    """The module at `path`, loaded by path so that hyphenated names work."""
    if not path.is_file():
        raise FileNotFoundError(f"no {what} {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str):
    return _module(HERE / "metrics" / f"{name}.py", "metric reader",
                   f"chipbench_metric_{name}")


def arch(name: str):
    return _module(ARCHS / f"{name}.py", "architecture module",
                   f"chipbench_arch_{name}")


def cell(bench: Dict, workload: str) -> Dict:
    """The workload entry with its files resolved, and the metrics it
    reports: `end_to_end` and `per_layer`, each [(entry, reader)]."""
    try:
        w = next(x for x in bench["workloads"] if x["name"] == workload)
    except StopIteration:
        names = [x["name"] for x in bench["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {names}") from None
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])

    def readers(kind: str) -> List:
        return [(m, metric(m["name"])) for m in bench[kind]
                if workload in m.get("workloads", [workload])]

    return {
        "workload": w,
        "config": config(cfg_entry),
        "config_entry": cfg_entry,
        "traffic": traffic(w["traffic"]),
        "limits": limits(workload),
        "end_to_end": readers("end_to_end"),
        "per_layer": readers("per_layer"),
    }
