"""``chip_smoke.py`` on the CPU: its phases run small, its entry point
refuses a machine without a TPU, and the compile-cache path rule.

On the CPU every kernel resolves to its jnp reference, so each phase
compares the reference with itself — what this checks is the phases'
control flow and bookkeeping, not the kernels (the chip run does that;
the uplink phase also runs once with the interpret-mode kernel).
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.utils import compile_cache

ROOT = Path(chip_smoke.__file__).resolve().parent

TINY = {
    "paper": dict(n_clients=8, cohort=3, n_rounds=3, local_steps=3,
                  batch_size=4),
    "full": dict(n_clients=8, cohort=3, n_rounds=3, local_steps=2,
                 batch_size=4),
    "sweep": dict(n_clients=8, cohort=3, n_rounds=2, local_steps=2,
                  batch_size=4, loss_rates=(0.0, 0.3)),
    "uplink_large": dict(cohort=4, d_up=1000),
}


def _assert_passed(res):
    assert res["checks"], res
    for name, c in res["checks"].items():
        assert c["ok"], (res["phase"], name, c)
    assert res["compile_s"] > 0 and res["run_s"] > 0
    assert res["kernel_calls"] == 0             # nothing compiled for TPU


@pytest.mark.parametrize("phase", list(TINY))
def test_phase_runs_small_on_reference(phase):
    _assert_passed(chip_smoke.PHASES[phase](**TINY[phase]))


def test_uplink_phase_with_interpret_kernel(monkeypatch):
    monkeypatch.setenv("REPRO_UPLINK_IMPL", "kernel")
    res = chip_smoke.phase_uplink_large(**TINY["uplink_large"])
    _assert_passed(res)
    assert os.environ["REPRO_UPLINK_IMPL"] == "kernel"   # restored


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Without the rest of the repository the script cannot import the
    engine: it fails and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {**os.environ, "PYTHONPATH": "", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_dir_rule(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.compile_cache_dir() == "/somewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
