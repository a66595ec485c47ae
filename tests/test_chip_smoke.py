"""``chip_smoke.py`` rehearsed on the CPU at a small size.

The phase functions run as the script runs them on the chip, but on a
512-XPU cluster (8 cubes of 4^3, or an 8^3 static torus) with ~30
Philly-statistic jobs and the Pallas kernels interpreted: the same
parity against the numpy engine, the same zero-failover checks, and the
same refusal to report a result when no TPU is present.
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.kernels.fitmask.ops import PallasEngine

SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")
XPUS = 512
JOBS = 30


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("policy,policy_kw", [
    ("rfold", {"num_xpus": XPUS, "cube_n": 4}),
    ("firstfit", {"dims": (8, 8, 8)}),
], ids=["rfold", "firstfit"])
def test_served_phase_matches_numpy(smoke, policy, policy_kw):
    res = smoke.served_phase(policy, policy_kw,
                             smoke.philly_jobs(JOBS, 0, XPUS))
    assert res["jobs"] == JOBS
    assert 0 < res["placed"] <= JOBS


def test_fleet_phase_matches_numpy(smoke):
    res = smoke.fleet_phase(XPUS, JOBS, sims=4, seed=0)
    assert res["engine_calls"] >= 1
    assert res["jobs"] == 4 * JOBS
    assert 0 < res["placed"] <= res["jobs"]


def _broken(*_a, **_kw):
    raise RuntimeError("kernel refused")


def test_served_phase_fails_on_an_engine_error(smoke, monkeypatch):
    """The daemon turns a kernel error into an error reply and keeps
    running; the phase must fail on that reply."""
    monkeypatch.setattr(PallasEngine, "multibox", _broken)
    monkeypatch.setattr(PallasEngine, "free_counts", _broken)
    with pytest.raises(RuntimeError, match="kernel refused"):
        smoke.served_phase("rfold", {"num_xpus": XPUS, "cube_n": 4},
                           smoke.philly_jobs(6, 0, XPUS))


def test_fleet_phase_fails_on_a_failover(smoke, monkeypatch):
    """A broken device engine fails over to a working one, so the
    fleet's answers still match: the phase must fail on the failover
    itself, which the broker also reports as a warning."""
    monkeypatch.setattr(PallasEngine, "multibox", _broken)
    with pytest.warns(RuntimeWarning, match="kernel refused"), \
            pytest.raises(smoke.SmokeFailure, match="engine_failovers"):
        smoke.fleet_phase(XPUS, 3, sims=2, seed=0)


def test_main_reports_no_result_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_reports_no_result(tmp_path):
    """Copied into a directory that holds nothing else of the repo, the
    script cannot import the program and must exit non-zero."""
    shutil.copy(SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-elsewhere"])
def test_compile_cache_location(monkeypatch, env_dir):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, with no
    directory set in code; otherwise to <checkout>/.jax_cache."""
    import jax

    from repro import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        expect = os.path.join(os.path.dirname(SCRIPT), ".jax_cache")
        assert compile_cache.enable_compile_cache() == expect
        assert updates["jax_compilation_cache_dir"] == expect
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
