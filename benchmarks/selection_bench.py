"""Selection-policy grid: the full traced policy family × loss rates as
ONE compiled vmap(scan) program (emits BENCH_selection.json).

The cell runs every policy in ``repro.core.selection.POLICIES`` against
every loss rate with the policy one-hot riding ``ScenarioCtx``
(``traced=True``) — the compile count is asserted, so the benchmark
doubles as the acceptance check that a selection-policy × loss-rate
grid really is a single program. The FCC-calibrated client draw makes
the per-policy participation histograms directly comparable to the
paper's bias argument (§5): ``bandwidth_threshold`` starves the bottom
bandwidth quartile; ``uniform`` + TRA keeps every quartile at its
population share.

CPU-timing honesty: all scenarios share one CPU; the scenarios/sec
number measures vmap dispatch amortization (like BENCH_sweep), and the
traced one-hot contraction adds all five score vectors to every cell's
program — the point is one program for the whole family, not per-cell
FLOP savings.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench
from repro.configs.synthetic_mlp import MLPConfig
from repro.core.mlp import mlp_init
from repro.core.selection import POLICIES, SelectionConfig
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.netsim import NetSimConfig
from repro.network.trace import sample_networks

N_CLIENTS = 30
ROUNDS = 60
CPR = 10
SEED = 7
LOSS_RATES = (0.1, 0.2, 0.3)
TEMPERATURES = {"uniform": 1.0, "bandwidth_threshold": 0.05,
                "gradient_norm": 0.5, "loss_aware": 0.5,
                "netsim_state": 0.05,
                # no deadline/faults in this grid -> stale_mem/rep_mem
                # stay zero and these score as uniform; they ride along
                # so the benchmark keeps covering the FULL traced family
                "staleness_aware": 0.5, "reputation_aware": 0.5}


def _grid_cfgs():
    return [FLConfig(algo="fedavg", n_rounds=ROUNDS,
                     clients_per_round=CPR, local_steps=2, batch_size=8,
                     eval_every=10 ** 6, seed=SEED, engine="scan",
                     sel=SelectionConfig(policy=p, traced=True,
                                         temperature=TEMPERATURES[p]),
                     tra=TRAConfig(enabled=True, loss_rate=r),
                     netsim=NetSimConfig(channel="gilbert_elliott",
                                         burst_len=6.0))
            for p in POLICIES for r in LOSS_RATES]


def selection_policy_grid():
    """Headline selection numbers (emits BENCH_selection.json)."""
    data = generate_synthetic(np.random.default_rng(SEED),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)
    nets = sample_networks(np.random.default_rng(2026), N_CLIENTS)
    cfgs = _grid_cfgs()
    S = len(cfgs)
    mcfg = MLPConfig(d_hidden=16)

    def pinit(k):
        return mlp_init(k, mcfg)

    def run_sweep():
        eng = SweepEngine.from_configs(cfgs, data, nets)
        _, logs = eng.run_block(eng.init_states(pinit), 0, ROUNDS)
        return eng, logs

    eng, logs = run_sweep()               # warmup incl. compile
    n_compiled = int(eng._block._cache_size())
    # the acceptance criterion: the whole policy × loss grid is ONE
    # compiled vmap(scan) program
    assert n_compiled == 1, \
        f"policy grid compiled {n_compiled} programs, expected 1"
    t0 = time.time()
    run_sweep()
    sweep = time.time() - t0

    order = np.argsort(nets.upload_mbps)
    bottom_q, top_q = order[:N_CLIENTS // 4], order[-N_CLIENTS // 4:]
    slots = ROUNDS * CPR * len(LOSS_RATES)
    per_policy = {}
    for i, p in enumerate(POLICIES):
        rows = slice(i * len(LOSS_RATES), (i + 1) * len(LOSS_RATES))
        hist = np.bincount(logs["ids"][rows].ravel(),
                           minlength=N_CLIENTS)
        share = hist / slots
        per_policy[p] = {
            "participation_hist": hist.tolist(),
            "bottom_quartile_share": float(share[bottom_q].sum()),
            "top_quartile_share": float(share[top_q].sum()),
            "fairness_spread": float(share.std()),
            "final_loss": {str(r): float(logs["loss"][i * len(LOSS_RATES)
                                                      + j, -1])
                           for j, r in enumerate(LOSS_RATES)},
        }

    uni = per_policy["uniform"]["bottom_quartile_share"]
    thr = per_policy["bandwidth_threshold"]["bottom_quartile_share"]
    emit("BENCH_selection", 1e6 * sweep / (S * ROUNDS),
         f"policy×loss grid S{S} in ONE program "
         f"({S / sweep:.2f} scen/s); bottom-quartile share "
         f"uniform={uni:.2f} vs threshold={thr:.2f}")
    write_bench(
        "BENCH_selection",
        config={"policies": list(POLICIES), "loss_rates": LOSS_RATES,
                "scenarios": S, "rounds": ROUNDS,
                "n_clients": N_CLIENTS, "cohort": CPR,
                "temperatures": TEMPERATURES},
        cells=per_policy,
        honesty={
            "backend": jax.default_backend(),
            "note": "Single-CPU timing: scenarios/sec measures vmap "
                    "dispatch amortization across the policy family, "
                    "not accelerator wins; the traced one-hot puts all "
                    "five score vectors in every cell's program, which "
                    "is the price of compiling the family once.",
        },
        extra={
            "sweep_seconds": sweep,
            "sweep_scenarios_per_sec": S / sweep,
            "sweep_compiled_programs": n_compiled,
            "one_compile_for_grid": n_compiled == 1,
            "bias_margin_bottom_quartile": uni - thr,
        })


ALL = [selection_policy_grid]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
