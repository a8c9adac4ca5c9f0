"""`BENCHMARK.json` resolves by name, and the command refuses to run off
a TPU."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from chipbench import registry

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
           "chips": 1, "peak": None, "trace": None}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        v = registry.metric(m["name"]).read(ctx)
        assert v is None or m["name"] == "setup_s"


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


_TIMES = [0.5] * 18 + [0.6, 0.7]
_FILLED = {
    "times": _TIMES, "window_s": sum(_TIMES), "rounds": len(_TIMES),
    "setup_s": 12.5, "memory": [{}], "memory_peak": [9.5e9],
    "flops_per_round": 0.2 * 197e12 * 0.5, "chips": 1,
    "peak": {"bf16_flops_per_s": 197e12},
    "trace": {"idle_share": 0.03},
}
_EXPECTED = {
    "round_s": sum(_TIMES) / 20,
    "round_p90_s": 0.59,  # statistics.quantiles, exclusive: at rank 18.9 of 20
    "hbm_peak_gb": 9.5,
    "setup_s": 12.5,
    "mfu": 0.2 * 0.5 / (sum(_TIMES) / 20),
    "device_idle_share": 0.03,
}


@pytest.mark.parametrize("name", sorted(_EXPECTED))
def test_metric_reader_reads_a_filled_run(name):
    assert {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]} == set(_EXPECTED)
    assert registry.metric(name).read(_FILLED) == pytest.approx(_EXPECTED[name])


@pytest.mark.parametrize("value,ok", [(0.01, True), (0.3, False),
                                      (float("nan"), False), (None, False)])
def test_judge_holds_each_number_to_its_limit(value, ok):
    from chipbench import compare

    read = {} if value is None else {"update1_gap": {"value": value}}
    passed, checks = compare.judge(read, {"update1_gap": 0.2})
    assert passed is ok
    assert checks["update1_gap"]["limit"] == 0.2
