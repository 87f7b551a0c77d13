"""Fault-rate × defense grid: undefended vs screen+clip+trimmed-mean
cells as ONE compiled vmap(scan) program (emits BENCH_faults.json).

The grid traces every fault rate and defense gate (``FaultConfig`` /
``DefenseConfig`` riding ``ScenarioCtx``), so the defended and the
undefended cell share one program — the compile count is asserted, and
the benchmark doubles as the acceptance check that a corruption grid
really is a single program.

The headline number is the price of defense: the robust uplink adds a
finite-screen prepass (a second read of the (C, P, F) tensor), the
clip reduction and — when ``trim_k > 0`` — the coordinate-wise
extraction loop, all of it compiled into EVERY cell of the grid (the
gates are traced, not static). ``defended_overhead`` therefore
compares the whole fault grid against the SAME grid with the fault
subsystem compiled out (``faults.enabled=False``) — program-level
honesty, not a gated-off traced run pretending to be the baseline.

CPU-timing honesty: all scenarios share one CPU; scenarios/sec
measures vmap dispatch amortization (like BENCH_sweep/BENCH_async),
not accelerator wins, and the jnp reference (not the Pallas kernel)
is what runs off-TPU.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench
from repro.core.selection import SelectionConfig
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.netsim import NetSimConfig
from repro.netsim.faults import DefenseConfig, FaultConfig
from repro.network.trace import ClientNetworks

N_CLIENTS = 20
ROUNDS = 30
CPR = 12
SEED = 13
CORRUPT_RATES = (0.0, 0.1)
TRIM_K = 2


def _cfg(faults, defense):
    return FLConfig(algo="fedavg", n_rounds=ROUNDS,
                    clients_per_round=CPR, local_steps=2, batch_size=8,
                    eval_every=10 ** 6, seed=SEED, engine="scan",
                    error_feedback=False,
                    sel=SelectionConfig(),
                    tra=TRAConfig(enabled=True, loss_rate=0.3),
                    netsim=NetSimConfig(channel="gilbert_elliott",
                                        burst_len=8.0, deadline=True,
                                        deadline_s=60.0),
                    faults=faults, defense=defense)


def _grid_cfgs():
    defenses = {
        "undefended": DefenseConfig(trim_k=TRIM_K),
        "defended": DefenseConfig(screen=True, clip=True,
                                  clip_norm=20.0, trim=True,
                                  trim_k=TRIM_K),
    }
    return [(name, r,
             _cfg(FaultConfig(enabled=True, corrupt_rate=r,
                              corrupt_scale=0.5, fail_rate=r),
                  d))
            for name, d in defenses.items() for r in CORRUPT_RATES]


def fault_defense_grid():
    """Headline corruption-grid numbers (emits BENCH_faults.json)."""
    data = generate_synthetic(np.random.default_rng(SEED),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)
    nets = ClientNetworks(np.linspace(0.5, 20.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))
    cells = _grid_cfgs()
    cfgs = [c for _, _, c in cells]
    S = len(cfgs)

    def run_sweep(cs):
        eng = SweepEngine.from_configs(cs, data, nets)
        _, logs = eng.run_block(eng.init_states(), 0, ROUNDS)
        return eng, logs

    eng, logs = run_sweep(cfgs)           # warmup incl. compile
    n_compiled = int(eng._block._cache_size())
    # the acceptance criterion: the whole fault-rate × defense grid
    # is ONE compiled vmap(scan) program
    assert n_compiled == 1, \
        f"fault grid compiled {n_compiled} programs, expected 1"
    t0 = time.time()
    run_sweep(cfgs)
    sweep = time.time() - t0

    # program-level baseline: the same grid shape with the fault
    # subsystem compiled OUT — what the undefended engine costs
    base_cfgs = [_cfg(FaultConfig(), DefenseConfig())
                 for _ in range(S)]
    run_sweep(base_cfgs)                  # warmup
    t0 = time.time()
    run_sweep(base_cfgs)
    base = time.time() - t0

    per_cell = {}
    for i, (name, r, _) in enumerate(cells):
        per_cell[f"{name}@corrupt={r}"] = {
            "final_loss": float(np.asarray(logs["loss"])[i, -1]),
            "quarantined_packets": float(
                np.asarray(logs["quarantine"])[i].sum()),
        }

    emit("BENCH_faults", 1e6 * sweep / (S * ROUNDS),
         f"fault×defense grid S{S} in ONE program "
         f"({S / sweep:.2f} scen/s); defended-program overhead "
         f"{sweep / base:.2f}x vs faults compiled out")
    write_bench(
        "BENCH_faults",
        config={"corrupt_rates": CORRUPT_RATES, "trim_k": TRIM_K,
                "scenarios": S, "rounds": ROUNDS,
                "n_clients": N_CLIENTS, "cohort": CPR},
        cells=per_cell,
        honesty={
            "backend": jax.default_backend(),
            "note": "Single-CPU timing via the jnp reference (the "
                    "Pallas robust kernel runs on TPU); the overhead "
                    "ratio compares compiled-in fault+defense "
                    "machinery (screen prepass = a second (C,P,F) "
                    "read, clip reduction, trim_k extraction loop in "
                    "every cell) against the same grid with the "
                    "subsystem compiled out — the traced gates mean "
                    "undefended CELLS still pay for the defended "
                    "program.",
        },
        extra={
            "sweep_seconds": sweep,
            "sweep_scenarios_per_sec": S / sweep,
            "sweep_compiled_programs": n_compiled,
            "one_compile_for_grid": n_compiled == 1,
            "baseline_seconds_faults_compiled_out": base,
            "defended_overhead": sweep / base if base > 0
            else float("inf"),
        })


ALL = [fault_defense_grid]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
