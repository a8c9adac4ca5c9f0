"""`launch/train.py main(argv)` driven in-process on the CPU, at reduced
sizes: its result, the depth / vocabulary cuts, the compile-cache
location, and `chip_smoke.py` refusing to run without a TPU."""
import math
import pathlib
import runpy

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import train

REPO = pathlib.Path(__file__).resolve().parents[1]
TINY = ["--per-agent-batch", "1", "--seq-len", "32", "--local-steps", "2",
        "--agents", "2", "--log-every", "1"]


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """Keep `main` off the process-wide cache config: with the variable
    set it leaves the cache where JAX already put it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("runtime", ["sync", "async"])
def test_main_returns_finite_falling_losses(runtime, cache_env):
    res = train.main(["--arch", "granite-8b", "--reduced", "--rounds", "3",
                      "--runtime", runtime] + TINY)
    assert len(res.losses) == 3 and len(res.round_seconds) == 3
    assert all(math.isfinite(v) for v in res.losses)
    assert res.losses[-1] < res.losses[0]
    assert all(
        bool(np.all(np.isfinite(np.asarray(u))))
        for u in jax.tree.leaves((res.params, res.delta))
    )
    if runtime == "sync":
        assert res.compile_seconds > 0


def test_profile_rounds_trace_holds_a_round_span_each(cache_env, tmp_path):
    """`--telemetry DIR --profile-rounds 1,2`: one trace under DIR/profile
    whose host spans hold one `round` per profiled round, found by the
    benchmark's trace reader."""
    import sys

    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from chipbench import trace

    d = tmp_path / "telemetry"
    train.main(["--arch", "granite-8b", "--reduced", "--rounds", "4",
                "--telemetry", str(d), "--profile-rounds", "1,2"] + TINY)
    host = trace.load(trace.find_xplane(str(d / "profile")))["host"]
    assert [n for n, _, _ in host if n == "round"] == ["round"] * 2


def test_cuts_keep_widths(cache_env):
    res = train.main(["--arch", "granite-8b", "--reduced", "--rounds", "1",
                      "--num-layers", "3", "--vocab-size", "100"] + TINY)
    reduced = get_config("granite-8b").reduced()
    assert (res.cfg.num_layers, res.cfg.vocab_size) == (3, 100)
    assert (res.cfg.d_model, res.cfg.d_ff, res.cfg.num_heads) == (
        reduced.d_model, reduced.d_ff, reduced.num_heads
    )
    assert res.params["embed"].shape == (100, reduced.d_model)
    assert int(np.max(np.asarray(res.data["tokens"]))) < 100


@pytest.mark.parametrize("cut", [
    ["--arch", "gemma2-2b", "--num-layers", "3"],  # 2-layer local/global
    ["--arch", "granite-8b", "--num-layers", "0"],
    ["--arch", "granite-8b", "--vocab-size", "49153"],
])
def test_cut_that_is_not_a_share_is_rejected(cut, cache_env):
    with pytest.raises(SystemExit) as e:
        train.main(cut + ["--reduced", "--rounds", "1"] + TINY)
    assert e.value.code == 2


def test_compile_cache_follows_env_else_repo(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert train.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert train.configure_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys):
    assert jax.default_backend() != "tpu"
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as e:
        runpy.run_path(str(REPO / "chip_smoke.py"), run_name="__main__")
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
