# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# Headline JSONs land in benchmarks/results/: BENCH_sweep.json (grid
# amortization), BENCH_uplink_fused.json (megakernel HBM-pass
# accounting: fused = 1 read of the (C, P, F) uploads, unfused >= 3),
# BENCH_netsim.json (on-device Gilbert-Elliott mask generation +
# burst-grid scenarios/sec), BENCH_selection.json (the traced
# selection-policy x loss-rate grid as one program + per-policy
# participation/bias histograms) and BENCH_async.json (the traced
# server-mode x loss-rate grid as one program + per-mode final loss
# and slow-quartile arrival shares).
import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="LT-FL benchmark suite",
        epilog="headline artifacts: results/BENCH_sweep.json, "
               "results/BENCH_uplink_fused.json (see docs/EXPERIMENTS.md)")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark names "
                         "(e.g. --only uplink)")
    ap.add_argument("--skip-fl", action="store_true",
                    help="skip the (slower) federated-learning figures")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace (TensorBoard/"
                         "Perfetto) covering the selected benchmarks")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.profile_dir:
        import jax
        jax.profiler.start_trace(args.profile_dir)

    from benchmarks import (async_bench, beyond, engine_bench,
                            faults_bench, kernel_bench, netsim_bench,
                            paper_figures, recovery_bench, roofline,
                            selection_bench, sweep_bench)

    benches = list(kernel_bench.ALL)
    if not args.skip_fl:
        benches += list(paper_figures.ALL) + list(beyond.ALL) \
            + list(engine_bench.ALL) + list(sweep_bench.ALL) \
            + list(netsim_bench.ALL) + list(selection_bench.ALL) \
            + list(async_bench.ALL) + list(faults_bench.ALL) \
            + list(recovery_bench.ALL)
    benches += list(roofline.ALL)

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report all, fail at end
            failures += 1
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s, failures={failures}",
          file=sys.stderr)
    if args.profile_dir:
        import jax
        jax.profiler.stop_trace()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
