"""A whole run of the benchmark, past its look for a chip, on a small
cell of each kind: sound, it comes out correct; with the timed path
broken underneath (`chipbench.faults`) it comes out not correct."""
import time

import pytest

from chipbench import faults, harness

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
