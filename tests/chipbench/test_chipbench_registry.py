"""`BENCHMARK.json` resolves by name, every metric reader reads a filled
run, a new metric is new files alone, and the command refuses to run
off a TPU."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import registry, scopes, trace

import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_its_files(workload):
    cell = registry.cell(BENCH, workload)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["traffic"]["algorithm"] == "fedgda_gt"
    assert cell["limits"] and all(v > 0 for v in cell["limits"].values())
    names = {m["name"] for m, _ in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for _, reader in cell["end_to_end"] + cell["per_layer"]:
        assert callable(reader.read)


def test_names_files_and_paths_keep_the_contract():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert set(c["reduced"]) <= set(json.loads((ROOT / c["file"]).read_text())["reduced"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_metric_readers_give_nothing_where_there_is_nothing_to_read():
    ctx = {"times": [], "window_s": 0.0, "rounds": 0, "setup_s": 1.0,
           "memory": [None], "memory_peak": [None], "flops_per_round": 1.0,
           "chips": 1, "peak": None, "trace": None, "config": {}, "traffic": {}}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        v = registry.metric(m["name"]).read(ctx)
        assert v is None or m["name"] == "setup_s"


def test_scope_and_kernel_readers_give_nothing_for_a_program_without_names():
    """A traced round whose program names no phase (the parent of the
    phase names) and runs no scan kernel: the readers of both say
    nothing, and `device_idle_share` still reads."""
    ev = {"devices": {"/device:TPU:0": [("fusion.1", 0, 10)]},
          "host": [("round", 0, 20)]}
    summary = dict(trace.reduce(ev), scopes=scopes.reduce(ev, {}))
    ctx = dict(_FILLED, trace=summary)
    for m in BENCH["per_layer"]:
        v = registry.metric(m["name"]).read(ctx)
        assert (v is None) == (m["name"] not in ("mfu", "device_idle_share")), m["name"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_reads_the_metrics_that_list_it_or_list_none(workload):
    cell = registry.cell(BENCH, workload)
    names = {m["name"] for m, _ in cell["per_layer"]}
    listed = {m["name"] for m in BENCH["per_layer"] if "workloads" in m}
    assert names - listed == {m["name"] for m in BENCH["per_layer"]} - listed
    assert ("ssm_scan_s" in names) == workload.startswith("falcon-mamba-7b.")
    assert ("ssm_scan_roofline" in names) == ("ssm_scan_s" in names)
    assert {"mfu", "broadcast_s", "unscoped_share"} <= names


def test_run_exits_non_zero_naming_the_platform_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


_MAMBA = registry.cell(BENCH, "falcon-mamba-7b.gt.m2.s512")
_TIMES = [0.5] * 18 + [0.6, 0.7]
_FILLED = {
    "times": _TIMES, "window_s": sum(_TIMES), "rounds": len(_TIMES),
    "setup_s": 12.5, "memory": [{}], "memory_peak": [9.5e9],
    "flops_per_round": 0.2 * 197e12 * 0.5, "chips": 1,
    "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "trace": {"idle_share": 0.03, "scopes": {
        "broadcast_s": 0.0013, "exchange_corrections_s": 0.016,
        "local_steps_s": 0.010, "aggregate_s": 0.0057, "unscoped_s": 0.0,
        "loss_grad_s": 0.020, "busy_s": 0.033, "unscoped_share": 0.0,
        "named_ops": 900, "rounds": 5,
        "scope_s": {"local_steps": 0.010, "ssm_scan": 0.0034},
        "kernel_s": {"ssm_scan_fwd": 0.0008, "ssm_scan_bwd": 0.0026,
                     "fusion": 0.025}}},
    "config": _MAMBA["config"], "traffic": _MAMBA["traffic"],
}
_EXPECTED = {
    "round_s": sum(_TIMES) / 20,
    "round_p90_s": 0.59,  # statistics.quantiles, exclusive: at rank 18.9 of 20
    "hbm_peak_gb": 9.5,
    "setup_s": 12.5,
    "mfu": 0.2 * 0.5 / (sum(_TIMES) / 20),
    "device_idle_share": 0.03,
    "broadcast_s": 0.0013,
    "exchange_corrections_s": 0.016,
    "local_steps_s": 0.010,
    "aggregate_s": 0.0057,
    "loss_grad_s": 0.020,
    "unscoped_share": 0.0,
    "ssm_scan_s": 0.0034,
    # the mamba cell's scan calls move 541,851,648 bytes a round
    "ssm_scan_roofline": 100 * 541851648 / 819e9 / 0.0034,
}


def _expectation(name):
    """(ctx, value): the filled run above, or the `EXAMPLE` that a
    reader brings in its own module."""
    if name in _EXPECTED:
        return _FILLED, _EXPECTED[name]
    example = getattr(registry.metric(name), "EXAMPLE", None)
    assert example is not None, f"metric {name} has no filled-run expectation"
    return example


def _metric_names(bench):
    return sorted(m["name"] for m in bench["end_to_end"] + bench["per_layer"])


@pytest.mark.parametrize("name", _metric_names(BENCH))
def test_metric_reader_reads_a_filled_run(name):
    assert set(_EXPECTED) <= set(_metric_names(BENCH))
    ctx, value = _expectation(name)
    assert registry.metric(name).read(ctx) == pytest.approx(value)


_MLA_S = '''"""Device seconds per round under the `mla` scope."""

EXAMPLE = ({"trace": {"scopes": {"scope_s": {"mla": 0.25}}}}, 0.25)


def read(ctx):
    s = ctx["trace"] and ctx["trace"]["scopes"]["scope_s"]
    return s.get("mla") if s else None
'''
_FLASH_MLA_ROOFLINE = '''"""The `flash_mla` kernel's share of its roofline, in %."""
from chipbench import peaks, registry


def read(ctx):
    k = ctx["trace"] and ctx["trace"]["scopes"]["kernel_s"]
    if not k or "flash_mla" not in k:
        return None
    c = ctx["config"]
    arch = registry.arch(c["arch"])
    return 100.0 * peaks.share(
        k["flash_mla"], ctx["peak"],
        bytes=arch.kernel_bytes(c, ctx["traffic"])["flash_mla"],
        flops=arch.kernel_flops(c, ctx["traffic"])["flash_mla"])


EXAMPLE = ({"trace": {"scopes": {"kernel_s": {"flash_mla": 2e-3}}},
            "config": {"arch": "tiny-mla", "hidden_size": 256},
            "traffic": {"seq_len": 64},
            "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}},
           100 * 1e6 * 256 * 64 / 197e12 / 2e-3)
'''
_KERNEL_COUNTS = '''

def kernel_bytes(c, traffic):
    return {"flash_mla": 4.0 * c["hidden_size"] * traffic["seq_len"]}


def kernel_flops(c, traffic):
    return {"flash_mla": 1e6 * c["hidden_size"] * traffic["seq_len"]}
'''
_MLA_HLO = """HloModule jit_round, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.2 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(round)/local_steps/loss_grad/mla/dot_general"}
}

ENTRY %main.3 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.4 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %flash_mla.5 = f32[4]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/local_steps/loss_grad/mla/flash_mla/pallas_call"}
  ROOT %add.6 = f32[4]{0} add(%flash_mla.5, %Arg_0.1), metadata={op_name="jit(round)/aggregate/add"}
}
"""


def test_a_new_scope_and_kernel_metric_is_new_files_alone(tmp_path, monkeypatch):
    """A later architecture names a scope (`mla`) and runs a kernel of its
    own (`flash_mla`).  In a copy of the benchmark, its configuration
    and architecture module (granite's, with the kernel's bytes and
    FLOPs), a reader of each, and entries in `BENCHMARK.json` that list
    its cell, read a trace of its round; no file that was there
    changes."""
    before = tiny.digest(registry.HERE)
    here = tmp_path / "chipbench"
    shutil.copytree(registry.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    copied = tiny.digest(here)
    (here / "metrics" / "mla_s.py").write_text(_MLA_S)
    (here / "metrics" / "flash_mla_roofline.py").write_text(_FLASH_MLA_ROOFLINE)
    (here / "archs" / "tiny-mla.py").write_text(
        (here / "archs" / "granite-8b.py").read_text() + _KERNEL_COUNTS)
    (here / "configs" / "tiny-mla.json").write_text(
        json.dumps(dict(tiny.DENSE, name="tiny-mla", arch="tiny-mla", reduced=[])))
    w = "tiny-mla.gt.m8.b4s64"
    shutil.copy(here / "limits" / "granite-8b.gt.m8.b4s64.json", here / "limits" / f"{w}.json")
    bench = registry.load_benchmark()
    bench["configs"].append({"name": "tiny-mla", "source": "https://example.org/tiny-mla",
                             "file": "chipbench/configs/tiny-mla.json", "reduced": [],
                             "why": "latent attention"})
    bench["workloads"].append({"name": w, "config": "tiny-mla", "traffic": "gt.m8.b4s64",
                               "chips": 1, "why": "latent attention and its kernel"})
    bench["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": "device_trace", "layer": layer,
         "moves": "round_s", "workloads": [w]}
        for n, u, b, layer in [("mla_s", "s", "lower", "fused round program (engine and model)"),
                               ("flash_mla_roofline", "%", "higher", "kernels")]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setattr(registry, "HERE", here)
    monkeypatch.setattr(registry, "ARCHS", here / "archs")
    monkeypatch.setattr(registry, "ROOT", tmp_path)
    bench = registry.load_benchmark(tmp_path)
    cell = registry.cell(bench, w)
    readers = {m["name"]: r for m, r in cell["per_layer"]}
    assert {"mla_s", "flash_mla_roofline", "broadcast_s"} <= set(readers)
    assert "ssm_scan_s" not in readers
    assert "mla_s" not in {m["name"] for m, _ in registry.cell(bench, _MAMBA["workload"]["name"])["per_layer"]}

    # one round: the mla fusion [0, 2) ms, the kernel [2, 6), the aggregate [6, 7)
    ms = 1e6
    ev = {"devices": {"/device:TPU:0": [("fusion.4", 0, 2 * ms), ("flash_mla.5", 2 * ms, 4 * ms),
                                       ("add.6", 6 * ms, 1 * ms)]},
          "host": [("round", 0, 8 * ms)]}
    summary = dict(trace.reduce(ev), scopes=scopes.reduce(ev, scopes.op_scopes(_MLA_HLO)))
    ctx = dict(_FILLED, trace=summary, config=cell["config"], traffic=cell["traffic"])
    got = {n: readers[n].read(ctx) for n in ("mla_s", "flash_mla_roofline",
                                             "local_steps_s", "aggregate_s")}
    flash_bytes, flash_flops = 4.0 * 256 * 64, 1e6 * 256 * 64
    assert got == pytest.approx({
        "mla_s": 6e-3, "local_steps_s": 6e-3, "aggregate_s": 1e-3,
        "flash_mla_roofline": 100 * max(flash_bytes / 819e9, flash_flops / 197e12) / 4e-3})
    for name in ("mla_s", "flash_mla_roofline"):
        ex_ctx, value = _expectation(name)
        assert readers[name].read(ex_ctx) == pytest.approx(value)
    monkeypatch.undo()

    assert {k: v for k, v in tiny.digest(here).items() if k in copied} == copied
    assert tiny.digest(registry.HERE) == before


@pytest.mark.parametrize("value,ok", [(0.01, True), (0.3, False),
                                      (float("nan"), False), (None, False)])
def test_judge_holds_each_number_to_its_limit(value, ok):
    from chipbench import compare

    read = {} if value is None else {"update1_gap": {"value": value}}
    passed, checks = compare.judge(read, {"update1_gap": 0.2})
    assert passed is ok
    assert checks["update1_gap"]["limit"] == 0.2
