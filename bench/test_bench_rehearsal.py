"""CPU rehearsal of the benchmark: every cell's set-up, window and
comparison run through the harness at a tiny size, the result line has
the five required keys first and the compared numbers last, nothing
compiles in the window, and the command
refuses to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]
SEED = 2 ** 31 + 5
END_TO_END = {"client_updates_per_s", "block_p95_ms", "peak_hbm_mib",
              "setup_s"}


def tiny_spec(name: str) -> dict:
    """The cell with 12 clients, cohorts of 4 and blocks of 2 rounds of
    2 local steps of 4 samples (the model keeps its widths)."""
    spec = harness.cell_spec(name)
    cfg = json.loads(json.dumps(spec["config"]))
    cfg["data"]["n_clients"] = 12
    cfg["fl"].update(clients_per_round=4, local_steps=2, batch_size=4)
    spec["config"] = cfg
    spec["traffic"] = dict(spec["traffic"], block_rounds=2)
    return spec


def run_tiny(name: str, trace: bool = False):
    lines = []
    out = harness.run_cell(name, SEED, 0.0, trace, require_chip=False,
                           cache=False, log=lines.append,
                           spec=tiny_spec(name))
    return out, [json.loads(ln) for ln in lines if ln.startswith("{")]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_on_cpu(name):
    out, logged = run_tiny(name)
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == END_TO_END
    assert all(m["value"] >= 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    window = next(x["window"] for x in logged if "window" in x)
    assert window["xla_compiles"] == 0
    assert window["registry_compiles"] == 0
    assert window["registry_misses"] == 0
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_per_layer_metrics():
    out, _ = run_tiny("paper.single", trace=True)
    per_layer = {m["name"] for m in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["per_layer"]}
    assert set(out["metrics"]) <= per_layer
    # the host span is read on any backend; the device metrics need a
    # TPU trace and stay out of the line on the CPU
    assert "eval_ms" in out["metrics"]
    assert "device_idle_share" not in out["metrics"]


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper.single",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_without_a_tpu():
    p = _run_py(harness.ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_py_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".traces",
                                                  "__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused(monkeypatch):
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v9 imaginary")
