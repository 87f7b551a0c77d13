"""The control fails the comparison: the plain reference put in the
program's place with its matrix products one precision step below the
configuration's (float8 e4m3 operands for bfloat16), at each cell's own
size, from the seeds its limits were not set from."""
import pytest

from bench import compare, harness
from bench.calibrate import CONTROL_DTYPE

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    spec = harness.cell_spec(name)
    run = harness.CellRun(spec, 2 ** 31 + 13)
    run.free()
    k, n = run.k, harness.SETUP_BLOCKS
    clean = compare.reference_blocks(harness.reference(run), run.params0,
                                     k, n)
    control = compare.reference_blocks(
        harness.reference(run, dot_dtype=CONTROL_DTYPE), run.params0, k, n)
    checks = compare.hold(compare.numbers(control, clean,
                                          compare.as_float64(run.params0),
                                          k, n),
                          spec["limits"])
    assert not all(c["ok"] for c in checks.values()), checks
