"""Sweep-engine throughput: S scenarios as ONE vmap(scan) program vs S
sequential single-scenario round-scan engine runs.

Two workloads at 100 clients on a shared Synthetic(1,1) draw (the
seed x loss-rate grid shape, where scenarios share the dataset and the
sweep engine stages it once and broadcasts it through the vmap):

  probe   the dispatch-bound sweep setting — FedSGD-style probe grid
          (1 local step, cohort 2, batch 2, d_hidden=16 MLP) where
          per-round compute is tiny and the sequential path is bounded
          by fixed per-op dispatch overhead inside its scan. This is
          where the sweep's >=2x (ISSUE 2 acceptance) lives: the fixed
          overhead is paid once per round for the whole grid instead of
          once per scenario.
  paper   the paper's evaluation config (cohort 10, batch 8, the
          128-hidden MLP) — per-scenario local training is genuine
          compute that batching cannot amortize on CPU, so the sweep
          is ~parity there; reported to bound expectations.

Timing protocol: a timed "cell run" is everything a grid driver pays
per scenario — engine construction (device staging of the dataset,
eligibility masks), state init, all rounds, log flush. The first pass
is untimed warmup; it populates the shared compiled-program caches
(engine._STEP_CACHE / sweep._SWEEP_CACHE), so the timed passes exclude
compile on BOTH paths (compile time is reported separately as
first-pass minus best-pass). The sweep engine compiles exactly once
for the whole grid (asserted via the jit cache and recorded in the
JSON); execution-only times (pre-built engines, run_block only) are
also reported for transparency.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench
from repro.configs.synthetic_mlp import MLPConfig
from repro.core.engine import RoundScanEngine
from repro.core.mlp import mlp_init
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine, scenario_from_config
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.network.trace import ClientNetworks

N_CLIENTS = 100
ROUNDS = 200
SEED0 = 7
LOSS_RATES = (0.1, 0.2, 0.3)

PROBE = dict(clients_per_round=2, batch_size=2, d_hidden=16)
PAPER = dict(clients_per_round=10, batch_size=8, d_hidden=128)


def _grid(S, wl):
    return [FLConfig(algo="fedavg", n_rounds=ROUNDS,
                     clients_per_round=wl["clients_per_round"],
                     local_steps=1, batch_size=wl["batch_size"],
                     eval_every=10 ** 6, seed=SEED0 + s, engine="scan",
                     tra=TRAConfig(enabled=True,
                                   loss_rate=LOSS_RATES[s % 3]))
            for s in range(S)]


def _param_init(wl):
    mcfg = MLPConfig(d_hidden=wl["d_hidden"])
    return lambda key: mlp_init(key, mcfg)


def _bench_sweep(cfgs, data, nets, pinit, reps=3):
    def run_cells():
        """One whole-grid run: construct (stage once), init, scan."""
        eng = SweepEngine.from_configs(cfgs, data, nets)
        eng.run_block(eng.init_states(pinit), 0, ROUNDS)
        return eng

    def cache_size():
        return int(SweepEngine.from_configs(
            cfgs, data, nets)._block._cache_size())

    before = cache_size()
    t0 = time.time()
    eng = run_cells()                          # warmup incl compile
    first = time.time() - t0
    # compiles THIS grid added to the shared sweep-program cache (the
    # jit wrapper is shared across grids with the same static config,
    # so the absolute cache size counts other grids' shapes too)
    n_compiles = cache_size() - before
    best = first
    for _ in range(reps):
        t0 = time.time()
        run_cells()
        best = min(best, time.time() - t0)
    # execution only: pre-built engine, run_block on fresh states
    states = eng.init_states(pinit)
    t0 = time.time()
    eng.run_block(states, 0, ROUNDS)
    exec_only = time.time() - t0
    return best, max(first - best, 0.0), exec_only, n_compiles


def _bench_sequential(cfgs, data, nets, pinit, reps=3):
    def run_cells():
        """S per-cell engine runs: construct (stage per cell), init,
        scan — the grid loop the sweep engine replaces."""
        engines = []
        for c in cfgs:
            s = scenario_from_config(c, data, nets)
            e = RoundScanEngine(c, data, s.sufficient, s.eligible)
            e.run_block(e.init_state(pinit(jax.random.PRNGKey(c.seed))),
                        0, ROUNDS)
            engines.append(e)
        return engines

    t0 = time.time()
    engines = run_cells()                      # warmup incl compile
    first = time.time() - t0
    best = first
    for _ in range(reps):
        t0 = time.time()
        run_cells()
        best = min(best, time.time() - t0)
    # execution only: pre-built engines, run_block on fresh states
    sts = [e.init_state(pinit(jax.random.PRNGKey(c.seed)))
           for e, c in zip(engines, cfgs)]
    t0 = time.time()
    for e, st in zip(engines, sts):
        e.run_block(st, 0, ROUNDS)
    exec_only = time.time() - t0
    return best, max(first - best, 0.0), exec_only


def sweep_vs_sequential():
    """Headline grid-amortization numbers (emits BENCH_sweep.json)."""
    data = generate_synthetic(np.random.default_rng(SEED0),
                              n_clients=N_CLIENTS, alpha=1.0, beta=1.0)
    nets = ClientNetworks(np.linspace(0.5, 24.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))
    rows = {"config": {"n_clients": N_CLIENTS, "rounds": ROUNDS,
                       "local_steps": 1, "loss_rates": LOSS_RATES,
                       "probe": PROBE, "paper": PAPER},
            "cells": {}}

    def cell(S, wl):
        cfgs = _grid(S, wl)
        pinit = _param_init(wl)
        sw, sw_compile, sw_exec, n_compiles = _bench_sweep(
            cfgs, data, nets, pinit)
        sq, sq_compile, sq_exec = _bench_sequential(cfgs, data, nets,
                                                    pinit)
        return {
            "scenarios": S,
            "sweep_seconds": sw, "sweep_compile_seconds": sw_compile,
            "sweep_exec_only_seconds": sw_exec,
            "sweep_n_compiles": n_compiles,
            "sequential_seconds": sq,
            "sequential_compile_seconds": sq_compile,
            "sequential_exec_only_seconds": sq_exec,
            "sweep_scenarios_per_sec": S / sw,
            "sequential_scenarios_per_sec": S / sq,
            "speedup_excl_compile": sq / sw,
            "speedup_exec_only": sq_exec / sw_exec,
        }

    for S in (1, 4, 16):
        rows["cells"][f"probe_S{S}"] = cell(S, PROBE)
    rows["cells"]["paper_S16"] = cell(16, PAPER)

    c16 = rows["cells"]["probe_S16"]
    acceptance = {
        "speedup_S16_dispatch_bound": c16["speedup_excl_compile"],
        "one_compile_for_grid": c16["sweep_n_compiles"] in (1, -1),
    }
    emit("BENCH_sweep", 1e6 * c16["sweep_seconds"] / (16 * ROUNDS),
         f"probe S16 {c16['speedup_excl_compile']:.1f}x vs sequential "
         f"(sweep {c16['sweep_scenarios_per_sec']:.2f} vs "
         f"{c16['sequential_scenarios_per_sec']:.2f} scen/s, exec-only "
         f"{c16['speedup_exec_only']:.1f}x, compile "
         f"{c16['sweep_compile_seconds']:.1f}s once; paper cfg "
         f"{rows['cells']['paper_S16']['speedup_excl_compile']:.1f}x)")
    write_bench(
        "BENCH_sweep", config=rows["config"], cells=rows["cells"],
        honesty={
            "backend": jax.default_backend(),
            "note": "Single-CPU timing: the probe workload is "
                    "dispatch-bound by design, so the speedup measures "
                    "vmap dispatch amortization (S scenarios, one "
                    "program) rather than extra FLOPs; the paper-config "
                    "cell shows what survives on a compute-bound "
                    "workload.",
        },
        extra={"acceptance": acceptance})


ALL = [sweep_vs_sequential]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
