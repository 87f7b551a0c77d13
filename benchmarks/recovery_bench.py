"""Recovery-policy × loss-rate grid: one_shot vs FEC vs ARQ cells as
ONE compiled vmap(scan) program (emits BENCH_recovery.json).

The grid traces the recovery policy one-hot, the retry budget and the
loss rate (``RecoveryConfig`` riding ``ScenarioCtx``), so every policy
shares one program — the compile count is asserted, and the benchmark
doubles as the acceptance check that a recovery grid really is a
single program.

The headline numbers are (a) the price of the recovery machinery: a
traced-recovery grid always draws BOTH the ARQ redraw block and the
FEC parity block (threefry uniforms are not prefix-stable in total
draw count, so the one-hot cells cannot skip draws and stay bitwise),
plus the group-repair prepass and the per-policy expected-sends cost
model — compared against the SAME grid with recovery compiled out;
and (b) the effective residual loss per policy: the realized
post-recovery drop fraction per cell next to the closed-form
prediction (one_shot r, arq r^(1+m), fec r·(1-(1-r)^G)).

CPU-timing honesty: all scenarios share one CPU; scenarios/sec
measures vmap dispatch amortization (like BENCH_sweep/BENCH_faults),
not accelerator wins, and the jnp FEC reference (not the Pallas
kernel) is what runs off-TPU.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench
from repro.core.selection import SelectionConfig
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine
from repro.core.telemetry import TelemetryConfig
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.netsim import NetSimConfig, RecoveryConfig
from repro.netsim.recovery import RECOVERY_POLICIES, residual_loss_rate
from repro.network.trace import ClientNetworks

N_CLIENTS = 20
ROUNDS = 30
CPR = 12
SEED = 13
LOSS_RATES = (0.1, 0.3)
GROUP = 8
RETRIES = 2.0


def _cfg(policy, rate, *, recovery=True):
    kw = {"recovery": RecoveryConfig(policy=policy, traced=True,
                                     group=GROUP, retries=RETRIES)} \
        if recovery else {}
    return FLConfig(algo="fedavg", n_rounds=ROUNDS,
                    clients_per_round=CPR, local_steps=2, batch_size=8,
                    eval_every=10 ** 6, seed=SEED, engine="scan",
                    sel=SelectionConfig(),
                    tra=TRAConfig(enabled=True, loss_rate=rate),
                    netsim=NetSimConfig(channel="gilbert_elliott",
                                        burst_len=8.0, deadline=True,
                                        deadline_s=60.0),
                    telemetry=TelemetryConfig(level="scalars"), **kw)


def recovery_policy_grid():
    """Headline recovery-grid numbers (emits BENCH_recovery.json)."""
    data = generate_synthetic(np.random.default_rng(SEED),
                              n_clients=N_CLIENTS, alpha=0.5, beta=0.5)
    nets = ClientNetworks(np.linspace(0.5, 20.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))
    cells = [(p, r) for p in RECOVERY_POLICIES for r in LOSS_RATES]
    cfgs = [_cfg(p, r) for p, r in cells]
    S = len(cfgs)

    def run_sweep(cs):
        eng = SweepEngine.from_configs(cs, data, nets)
        _, logs = eng.run_block(eng.init_states(), 0, ROUNDS)
        return eng, logs

    eng, logs = run_sweep(cfgs)           # warmup incl. compile
    n_compiled = int(eng._block._cache_size())
    # the acceptance criterion: the whole policy × loss-rate grid is
    # ONE compiled vmap(scan) program
    assert n_compiled == 1, \
        f"recovery grid compiled {n_compiled} programs, expected 1"
    t0 = time.time()
    run_sweep(cfgs)
    sweep = time.time() - t0

    # program-level baseline: the same grid shape with the recovery
    # subsystem compiled OUT (legacy one_shot path, no extra uniforms,
    # no prepass) — what PR-9's engine costs on the same grid
    base_cfgs = [_cfg("one_shot", r, recovery=False)
                 for _, r in cells]
    run_sweep(base_cfgs)                  # warmup
    t0 = time.time()
    run_sweep(base_cfgs)
    base = time.time() - t0

    per_cell = {}
    loss = np.asarray(logs["loss"])
    fec = np.asarray(logs["tele/fec_recovered"])
    arq = np.asarray(logs["tele/arq_recovered"])
    chan = np.asarray(logs["tele/realized_loss"]) \
        if "tele/realized_loss" in logs else None
    for i, (p, r) in enumerate(cells):
        recovered = {"one_shot": 0.0, "fec": float(fec[i].mean()),
                     "arq": float(arq[i].mean())}[p]
        cell = {
            "final_loss": float(loss[i, -1]),
            "recovered_pkt_frac": recovered,
            "residual_rate_closed_form": float(residual_loss_rate(
                p, r, retries=RETRIES, group=GROUP)),
        }
        if chan is not None:
            cell["realized_channel_loss"] = float(chan[i].mean())
        per_cell[f"{p}@loss={r}"] = cell

    emit("BENCH_recovery", 1e6 * sweep / (S * ROUNDS),
         f"recovery×loss grid S{S} in ONE program "
         f"({S / sweep:.2f} scen/s); recovery-program overhead "
         f"{sweep / base:.2f}x vs recovery compiled out")
    write_bench(
        "BENCH_recovery",
        config={"policies": RECOVERY_POLICIES,
                "loss_rates": LOSS_RATES, "group": GROUP,
                "retries": RETRIES, "scenarios": S, "rounds": ROUNDS,
                "n_clients": N_CLIENTS, "cohort": CPR},
        cells=per_cell,
        honesty={
            "backend": jax.default_backend(),
            "note": "Single-CPU timing via the jnp FEC reference (the "
                    "Pallas group-repair kernel runs on TPU); the "
                    "overhead ratio compares compiled-in recovery "
                    "machinery (ARQ redraw + parity uniform blocks "
                    "drawn in EVERY cell — threefry draw-count "
                    "stability — plus the repair prepass and sends "
                    "cost model) against the same grid with recovery "
                    "compiled out.",
        },
        extra={
            "sweep_seconds": sweep,
            "sweep_scenarios_per_sec": S / sweep,
            "sweep_compiled_programs": n_compiled,
            "one_compile_for_grid": n_compiled == 1,
            "baseline_seconds_recovery_compiled_out": base,
            "recovery_overhead": sweep / base if base > 0
            else float("inf"),
        })


ALL = [recovery_policy_grid]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
