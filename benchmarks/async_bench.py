"""Server-mode grid: sync/semi_sync/async × loss rates as ONE compiled
vmap(scan) program (emits BENCH_async.json).

The grid traces the server mode itself (``AsyncConfig(traced=True)``,
the mode one-hot riding ``ScenarioCtx.srv_mode``) under a deadline the
slow-bandwidth quartile cannot meet, so the emitted numbers ARE the
paper-style robustness comparison: per-mode final loss and
slow-quartile arrival mass (under sync the chronically-late clients'
uploads never land and the quartile's share collapses; async keeps
folding them in, staleness-discounted — tools/async_smoke.py asserts
the exact-zero property for the always-late subset). The compile count
is asserted, so the benchmark doubles
as the acceptance check that a mode × loss-rate grid really is a
single program.

CPU-timing honesty: all scenarios share one CPU; scenarios/sec
measures vmap dispatch amortization (like BENCH_sweep/BENCH_selection),
and tracing the mode puts every mode's arrival arithmetic and the
K-slot buffer in each cell's program — the price of compiling the mode
family once, not a per-cell FLOP win.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench
from repro.core.async_agg import MODES, AsyncConfig
from repro.core.selection import SelectionConfig
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.netsim import NetSimConfig
from repro.network.trace import ClientNetworks

N_CLIENTS = 20
ROUNDS = 40
CPR = 8
SEED = 11
LOSS_RATES = (0.1, 0.3)
DEADLINE_S = 0.1       # ~0.3 Mbit MLP upload: the slow quartile misses
BUFFER_K = 16


def _grid_cfgs():
    return [FLConfig(algo="fedavg", n_rounds=ROUNDS,
                     clients_per_round=CPR, local_steps=2, batch_size=8,
                     eval_every=10 ** 6, seed=SEED, engine="scan",
                     error_feedback=True,
                     sel=SelectionConfig(),
                     tra=TRAConfig(enabled=True, loss_rate=r),
                     netsim=NetSimConfig(channel="gilbert_elliott",
                                         burst_len=8.0, deadline=True,
                                         deadline_s=DEADLINE_S),
                     srv=AsyncConfig(mode=m, traced=True,
                                     buffer_k=BUFFER_K))
            for m in MODES for r in LOSS_RATES]


def server_mode_grid():
    """Headline async-server numbers (emits BENCH_async.json)."""
    data = generate_synthetic(np.random.default_rng(SEED),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)
    nets = ClientNetworks(np.linspace(0.5, 20.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))
    cfgs = _grid_cfgs()
    S = len(cfgs)

    # default-size MLP on purpose: its ~0.3 Mbit upload against the
    # 0.1 s deadline is what makes the slow quartile chronically late
    def run_sweep():
        eng = SweepEngine.from_configs(cfgs, data, nets)
        _, logs = eng.run_block(eng.init_states(), 0, ROUNDS)
        return eng, logs

    eng, logs = run_sweep()               # warmup incl. compile
    n_compiled = int(eng._block._cache_size())
    # the acceptance criterion: the whole mode × loss grid is ONE
    # compiled vmap(scan) program
    assert n_compiled == 1, \
        f"mode grid compiled {n_compiled} programs, expected 1"
    t0 = time.time()
    run_sweep()
    sweep = time.time() - t0

    slow = np.argsort(nets.upload_mbps)[:N_CLIENTS // 4]
    per_mode = {}
    for i, m in enumerate(MODES):
        rows = slice(i * len(LOSS_RATES), (i + 1) * len(LOSS_RATES))
        mass = np.zeros(N_CLIENTS)
        np.add.at(mass, np.asarray(logs["ids"][rows]).ravel(),
                  np.asarray(logs["arrival"][rows]).ravel())
        total = mass.sum()
        per_mode[m] = {
            "final_loss": {str(r): float(
                logs["loss"][i * len(LOSS_RATES) + j, -1])
                for j, r in enumerate(LOSS_RATES)},
            "arrival_mass": float(total),
            "slow_quartile_arrival_share":
                float(mass[slow].sum() / total) if total else 0.0,
        }

    sync_share = per_mode["sync"]["slow_quartile_arrival_share"]
    async_share = per_mode["async"]["slow_quartile_arrival_share"]
    emit("BENCH_async", 1e6 * sweep / (S * ROUNDS),
         f"mode×loss grid S{S} in ONE program "
         f"({S / sweep:.2f} scen/s); slow-quartile arrival share "
         f"sync={sync_share:.2f} vs async={async_share:.2f}")
    write_bench(
        "BENCH_async",
        config={"modes": list(MODES), "loss_rates": LOSS_RATES,
                "scenarios": S, "rounds": ROUNDS,
                "n_clients": N_CLIENTS, "cohort": CPR,
                "deadline_s": DEADLINE_S, "buffer_k": BUFFER_K},
        cells=per_mode,
        honesty={
            "backend": jax.default_backend(),
            "note": "Single-CPU timing: scenarios/sec measures vmap "
                    "dispatch amortization across the mode family, not "
                    "accelerator wins; tracing the mode compiles every "
                    "mode's arrival arithmetic and the K-slot buffer "
                    "into each cell, which is the price of one program "
                    "for the whole grid.",
        },
        extra={
            "sweep_seconds": sweep,
            "sweep_scenarios_per_sec": S / sweep,
            "sweep_compiled_programs": n_compiled,
            "one_compile_for_grid": n_compiled == 1,
            "robustness_margin_slow_quartile": async_share - sync_share,
        })


ALL = [server_mode_grid]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
