"""A whole run of the benchmark, past its look for a chip, on a small
cell of each kind: sound, it comes out correct; with the timed path
broken underneath (`chipbench.faults`) it comes out not correct; traced,
it reads the per-layer metrics from the trace and the compiled round."""
import time

import pytest

from chipbench import faults, harness, registry, scopes

import tiny

CASES = [(k, f) for k in tiny.KINDS for f in faults.FAULTS]


def _run(kind):
    return harness.run_cell(tiny.cell(kind), 2**33 + 5, 0.2, False,
                            tiny.devices(), time.perf_counter(),
                            log=lambda s: None)


@pytest.mark.parametrize("kind", sorted(tiny.KINDS))
def test_sound_run_is_correct(kind):
    r = _run(kind)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 3
    assert list(r)[-1] == "checks"
    assert {"round_s", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("kind,fault", CASES)
def test_fault_makes_run_incorrect(kind, fault):
    with faults.FAULTS[fault]():
        r = _run(kind)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0


def test_traced_run_reads_the_phase_and_kernel_metrics(monkeypatch):
    """The trace path of a run on the tiny mamba cell.  Off a chip the
    profiler records no device, so its file is replaced by one round in
    which every instruction of the compiled round runs once, then two
    scan kernels (the CPU round runs the jnp scan), and the CPU is given
    the v5e's peaks; the rest, the scope map of the compiled round and
    the configuration that the roofline reads among it, is the run's
    own."""
    texts = []
    traced = harness.Program.traced

    def traced_and_kept(self, n):
        texts.append(self.rnd.as_text())
        return traced(self, n)

    kernels = ["ssm_scan_fwd.900", "ssm_scan_bwd.901"]
    monkeypatch.setattr(harness.Program, "traced", traced_and_kept)
    monkeypatch.setattr(harness.trace_mod, "find_xplane", lambda d: d)
    monkeypatch.setattr(harness.trace_mod, "load", lambda path: tiny.one_round(
        sorted(n for _, n, _, _ in scopes.instructions(texts[-1])) + kernels))
    monkeypatch.setitem(harness.peaks.PEAKS, tiny.devices()[0].device_kind,
                        harness.peaks.PEAKS["TPU v5 lite"])
    cell = tiny.cell("mamba")
    r = harness.run_cell(cell, 2**33 + 5, 0.2, True, tiny.devices(),
                         time.perf_counter(), log=lambda s: None)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    phases = [m[f"{p}_s"] for p in scopes.PHASES]
    assert min(phases) > 0 and m["loss_grad_s"] > 0 and 0 < m["unscoped_share"] < 1
    assert sum(phases) == pytest.approx(r["device"]["busy_s"] * (1 - m["unscoped_share"]))
    assert m["ssm_scan_s"] == pytest.approx(2 * 15e-6)  # one_round's two ops
    moved = registry.arch("falcon-mamba-7b").kernel_bytes(
        cell["config"], cell["traffic"])["ssm_scan"]
    assert m["ssm_scan_roofline"] == pytest.approx(100 * moved / 819e9 / 30e-6)
    assert {"device_ops", "idle_gaps"} == set(r["breakdown"])
