"""The control: the program's own bfloat16 path (parameters, `delta`
and updates in bfloat16, below the configuration's float32), held to
the real cell's limits at a small size, comes out not correct."""
import time

import jax.numpy as jnp
import pytest

from chipbench import harness

import tiny


@pytest.mark.parametrize("kind", sorted(tiny.KINDS))
def test_bfloat16_control_is_not_correct(kind):
    r = harness.run_cell(tiny.cell(kind), 7, 0.2, False, tiny.devices(),
                         time.perf_counter(), dtype=jnp.bfloat16,
                         log=lambda s: None)
    assert not r["correct"], r["checks"]
    # the update norms are what the lower precision breaks
    assert r["checks"]["update1_gap"]["value"] > r["checks"]["update1_gap"]["limit"]
