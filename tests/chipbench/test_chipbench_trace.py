"""The reduction from a profiler trace to the benchmark's device
numbers (`chipbench.trace`)."""
import pathlib

import pytest

from chipbench import trace

MS = 1e6  # ns


def _synthetic():
    # two devices; host: round [0, 10) ms holding dispatch [0, 1) and
    # block [1, 10), then round [10, 20) holding dispatch [10, 12)
    host = [("round", 0, 10 * MS), ("dispatch", 0, 1 * MS), ("block", 1 * MS, 9 * MS),
            ("round", 10 * MS, 10 * MS), ("dispatch", 10 * MS, 2 * MS),
            ("$engine.py:10 local_steps", 12 * MS, 8 * MS),
            ("$pjit.py:1 cache_miss", 12 * MS, 1 * MS)]
    dev0 = [("fusion.1", 1 * MS, 4 * MS), ("dot.2", 3 * MS, 4 * MS),  # overlap
            ("fusion.1", 13 * MS, 5 * MS)]
    dev1 = [("dot.2", 2 * MS, 16 * MS), ("copy.3", 30 * MS, 1 * MS)]  # outside
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "host": host}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = trace.reduce(_synthetic(), host_files=("engine.py",))
    assert r["window_s"] == pytest.approx(0.020)
    # device 0: [1, 7) and [13, 18) -> 11 ms; device 1: [2, 18) -> 16 ms
    assert r["busy_s_per_device"] == pytest.approx([0.011, 0.016])
    assert r["busy_s"] == pytest.approx(0.0135)
    assert r["idle_share"] == pytest.approx(1 - 0.0135 / 0.020)


def test_ops_and_gaps_are_ranked_and_named_by_host_activity():
    r = trace.reduce(_synthetic(), host_files=("engine.py",))
    ops = dict(r["device_ops"])
    assert ops["dot.2"] == pytest.approx((0.004 + 0.016) / 2)
    assert ops["fusion.1"] == pytest.approx(0.009 / 2)
    assert "copy.3" not in ops
    assert [n for n, _ in r["device_ops"]] == ["dot.2", "fusion.1"]
    gaps = dict(r["idle_gaps"])
    # dev0 gaps: [0,1) dispatch, [7,13) by its midpoint 10 the second
    # dispatch, [18,20) a frame of engine.py; dev1: [0,2) by its midpoint
    # 1 block, [18,20) engine.py
    assert gaps["dispatch"] == pytest.approx((0.001 + 0.006) / 2)
    assert gaps["block"] == pytest.approx(0.002 / 2)
    assert gaps["engine.py:10 local_steps"] == pytest.approx((0.002 + 0.002) / 2)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_frames_of_other_files_are_not_names():
    r = trace.reduce(_synthetic(), host_files=())
    assert all(not n.startswith("pjit") for n, _ in r["idle_gaps"])
    assert "round" in dict(r["idle_gaps"])  # [18, 20) falls back to the round


def test_a_trace_without_a_window_or_devices_is_refused():
    ev = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce({"devices": ev["devices"], "host": []})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": ev["host"]})


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace recorded here by `jax.profiler`: the harness's
    annotations come back as the host spans, in order and nested.  The
    CPU backend has no device plane, so the reduction refuses it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a))
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("round"):
            with jax.profiler.TraceAnnotation("dispatch"):
                r = f(a)
            with jax.profiler.TraceAnnotation("block"):
                r.block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev["host"] if n in ("round", "dispatch", "block")]
    assert names == ["round", "dispatch", "block"] * 3
    rounds = [(s, s + d) for n, s, d in ev["host"] if n == "round"]
    inner = [(s, s + d) for n, s, d in ev["host"] if n in ("dispatch", "block")]
    for k, (a0, b0) in enumerate(inner):
        lo, hi = rounds[k // 2]
        assert lo <= a0 <= b0 <= hi
    assert ev["devices"] == {}
    with pytest.raises(ValueError):
        trace.reduce(ev)


def test_reduces_a_trace_recorded_on_a_v5e():
    """`data/v5e_tiny.xplane.pb`: three rounds of a jitted 512 x 512
    matmul and tanh under the harness's annotations, recorded by
    `jax.profiler` on one TPU v5e.  The device plane and its op line are
    found by name, and the idle time is named by the host spans."""
    path = pathlib.Path(__file__).parent / "data" / "v5e_tiny.xplane.pb"
    ev = trace.load(str(path))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert len(ev["devices"]["/device:TPU:0"]) == 9  # 3 rounds x 3 ops
    r = trace.reduce(ev, host_files=())
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(0.00231184)
    assert r["busy_s"] == pytest.approx(3.139e-06)
    gaps = dict(r["idle_gaps"])
    assert gaps["block"] > gaps.get("dispatch", 0.0)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][0].startswith("%fusion")
