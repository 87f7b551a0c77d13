"""Launcher CLI smoke tests — the exact entry points the README documents."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the launchers turn JAX's persistent compile cache on; tests keep it off
ENV = {**os.environ, "PYTHONPATH": "src",
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


def _run(args, timeout=420):
    return subprocess.run([sys.executable, "-m", *args], cwd=ROOT, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli_reduced():
    out = _run(["repro.launch.train", "--arch", "qwen1.5-4b", "--reduced",
                "--steps", "3", "--batch", "2", "--seq", "32"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "step    2" in out.stdout


def test_serve_cli_reduced():
    out = _run(["repro.launch.serve", "--arch", "stablelm-3b", "--reduced",
                "--tokens", "4", "--prompt-len", "4"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "decoded 4 tokens" in out.stdout


def test_fl_train_cli_reduced():
    out = _run(["repro.launch.fl_train", "--arch", "stablelm-3b",
                "--reduced", "--steps", "3", "--clients", "2",
                "--insufficient", "1", "--seq", "32"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "round    2" in out.stdout


@pytest.mark.slow
def test_dryrun_cli_single_combo():
    env = dict(ENV)
    code = _run(["repro.launch.dryrun", "--arch", "xlstm-350m",
                 "--shape", "decode_32k", "--mesh", "pod",
                 "--sharding", "best"], timeout=560)
    assert code.returncode == 0, code.stderr[-2000:]
    assert " ok" in code.stdout
