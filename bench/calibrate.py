"""Readings that the comparison's limits are set from (PERF.md, "How
correct is decided"). Run on the chip, from the root of a checkout:

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3

For each of ``--seeds`` it builds the cell's program exactly as a run's
set-up does and prints the three numbers of ``compare.py`` against the
plain reference (the lower readings). For each of ``--control-seeds``
it prints the same numbers for the reference put in the program's
place: computed with float8 (e4m3) matrix-product operands, one step
below the bfloat16 operands the configuration runs (the control), and
with each fault the reference file can plant (its ``FAULTS``). One JSON
line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_DTYPE = "float8_e4m3fn"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    # the benchmark's own compile cache; JAX reads it as it is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache"
                                                    / "jax")
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    from bench import compare, harness, models

    spec = harness.cell_spec(args.workload)
    try:
        harness.device_info(spec["cell"]["chips"], True)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    enable_compile_cache()
    k, n_blocks = spec["traffic"]["block_rounds"], harness.SETUP_BLOCKS
    reference = harness.reference
    faults = models.load_reference(spec["config"]["reference"]).FAULTS

    def emit(kind, seed, numbers, t0):
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "seconds": time.time() - t0,
                          **numbers}), flush=True)

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.time()
        run = harness.CellRun(spec, seed)
        losses, snaps = [], {}
        for b in range(n_blocks):
            losses.append(run.block(b * k))
            if b in (0, n_blocks - 1):
                snaps[b + 1] = run.snapshot(ef=b > 0)
        prog = compare.program_readings(losses, snaps, n_blocks)
        run.free()
        ref = compare.reference_blocks(reference(run), run.params0, k,
                                       n_blocks)
        p0 = compare.as_float64(run.params0)
        emit("program", seed, compare.numbers(prog, ref, p0, k, n_blocks),
             t0)

    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t0 = time.time()
        run = harness.CellRun(spec, seed)
        run.free()
        p0 = compare.as_float64(run.params0)
        clean = compare.reference_blocks(reference(run), run.params0, k,
                                         n_blocks)
        for kind, kw in [("control", {"dot_dtype": CONTROL_DTYPE})] + [
                (f"fault:{f}", {"fault": f}) for f in faults]:
            t1 = time.time()
            other = compare.reference_blocks(reference(run, **kw),
                                             run.params0, k, n_blocks)
            emit(kind, seed, compare.numbers(other, clean, p0, k, n_blocks),
                 t1)
        emit("reference", seed, {}, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
