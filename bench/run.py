"""Run one benchmark cell once, from the root of a checkout:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is the result (see PERF.md); the last
lines of standard error are the numbers of the correctness comparison,
each beside its limit. Without a TPU, or with a chip ``bench/peaks.json``
does not list, it prints no result and exits non-zero. JAX's compile
cache is kept in ``bench/.cache/jax`` of the checkout, where nothing but
the benchmark writes.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache"
                                                / "jax")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
