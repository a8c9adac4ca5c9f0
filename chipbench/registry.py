"""Find a cell's files by the names in `BENCHMARK.json`.

- configuration `<c>`: the `file` of its entry, `chipbench/configs/<c>.json`;
- traffic mix `<t>`: `chipbench/traffic/<t>.json`;
- correctness limits of cell `<w>`: `chipbench/limits/<w>.json`;
- metric `<m>`: module `chipbench/metrics/<m>.py`, whose `read(ctx)`
  returns the number or None where the run has nothing to read; a cell
  reports the metrics whose readers find something in it.

A later cell, configuration or metric is a new file and a new entry in
`BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(kind: str, name: str) -> Dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def config(entry: Dict, root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / entry["file"]).read_text())


def traffic(name: str) -> Dict:
    return _json("traffic", name)


def limits(workload: str) -> Dict[str, float]:
    return _json("limits", workload)


def metric(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
        return [(m, metric(m["name"])) for m in bench[kind]]

    return {
        "workload": w,
        "config": config(cfg_entry),
        "config_entry": cfg_entry,
        "traffic": traffic(w["traffic"]),
        "limits": limits(workload),
        "end_to_end": readers("end_to_end"),
        "per_layer": readers("per_layer"),
    }
