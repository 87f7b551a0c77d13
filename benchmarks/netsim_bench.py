"""Netsim throughput: on-device Gilbert–Elliott mask generation vs a
host-side numpy sampler, and burst-grid scenarios/sec through the
sweep engine.

Two cells (emits BENCH_netsim.json):

  mask_gen    (C, P) GE delivery masks per second. The device path is
              what the engine actually runs in-scan: one threefry
              uniform block + the ``kernels/netsim_mask`` recurrence
              (compiled Pallas on TPU, the jnp ``lax.scan`` reference
              on CPU), jitted end-to-end. The host baseline is the
              per-packet numpy loop a non-device simulator would run
              (``netsim.channel.sample_ge_mask_numpy``) — per-round
              host sampling plus an H2D copy is exactly the traffic
              the device-resident design removes.
  burst_grid  a burst-length x loss-rate grid (>= 8 scenarios) run as
              ONE vmap(scan) program through ``SweepEngine`` with the
              Gilbert–Elliott channel on, vs the same cells run
              sequentially through per-cell ``RoundScanEngine`` runs.
              Timed passes exclude compile on both paths (warmup
              first); the sweep must compile exactly once.

CPU-timing honesty: on this benchmark's CPU backend the "device" mask
path is XLA-compiled jnp rather than the Pallas kernel, and both
contenders share the same silicon — the mask_gen ratio measures
vectorized-JIT vs interpreted-python sampling, not accelerator wins,
and the burst-grid speedup is dispatch-amortization (like
BENCH_sweep's probe cell), not extra FLOPs. The JSON carries this
cell so the numbers cannot be misread.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, write_bench
from repro.configs.synthetic_mlp import MLPConfig
from repro.core.engine import RoundScanEngine
from repro.core.mlp import mlp_init
from repro.core.server import FLConfig
from repro.core.sweep import SweepEngine, scenario_from_config
from repro.core.tra import TRAConfig
from repro.data.synthetic import generate_synthetic
from repro.kernels.netsim_mask.ops import ge_packet_mask, resolved_impl
from repro.netsim import (NetSimConfig, ge_transition_probs,
                          sample_ge_mask_numpy, stationary_bad_frac)
from repro.network.trace import ClientNetworks

N_CLIENTS = 50
ROUNDS = 100
SEED0 = 7
BURSTS = (2.0, 4.0, 8.0, 16.0)
RATES = (0.1, 0.3)

MASK_C, MASK_P = 256, 128


def _time(fn, reps=5):
    fn()                                  # warmup / compile
    best = np.inf
    for _ in range(reps):
        t0 = time.time()
        fn()
        best = min(best, time.time() - t0)
    return best


def _mask_gen_cell():
    rate, burst = 0.2, 8.0
    key = jax.random.PRNGKey(0)
    pi_b = stationary_bad_frac(rate, 0.0, 1.0)
    s0 = (jax.random.uniform(key, (MASK_C,)) < pi_b).astype(jnp.int32)
    p_gb, p_bg = ge_transition_probs(jnp.float32(rate),
                                     jnp.float32(burst), 0.0, 1.0)

    @jax.jit
    def device_masks(key, s0):
        u = jax.random.uniform(key, (2, MASK_C, MASK_P),
                               minval=1e-12, maxval=1.0)
        return ge_packet_mask(u[0], u[1], s0, p_gb, p_bg, 0.0, 1.0)

    def run_device():
        m, s = device_masks(key, s0)
        m.block_until_ready()

    rng = np.random.default_rng(0)

    def run_host():
        sample_ge_mask_numpy(rng, MASK_C, MASK_P, rate, burst)

    dev = _time(run_device)
    host = _time(run_host)
    masks = MASK_C
    return {
        "clients": MASK_C, "packets": MASK_P,
        "impl_device": resolved_impl(),
        "device_seconds": dev, "host_numpy_seconds": host,
        "device_masks_per_sec": masks / dev,
        "host_masks_per_sec": masks / host,
        "device_vs_host": host / dev,
    }


def _grid_cfgs():
    cells = [(b, r) for b in BURSTS for r in RATES]
    return [FLConfig(algo="fedavg", n_rounds=ROUNDS, clients_per_round=8,
                     local_steps=1, batch_size=4, eval_every=10 ** 6,
                     seed=SEED0 + i, engine="scan",
                     tra=TRAConfig(enabled=True, loss_rate=r),
                     netsim=NetSimConfig(channel="gilbert_elliott",
                                         burst_len=b))
            for i, (b, r) in enumerate(cells)]


def _burst_grid_cell():
    data = generate_synthetic(np.random.default_rng(SEED0),
                              n_clients=N_CLIENTS, alpha=1.0, beta=1.0)
    nets = ClientNetworks(np.linspace(0.5, 24.0, N_CLIENTS),
                          np.full(N_CLIENTS, 0.05))
    cfgs = _grid_cfgs()
    S = len(cfgs)
    mcfg = MLPConfig(d_hidden=16)

    def pinit(k):
        return mlp_init(k, mcfg)

    def run_sweep():
        eng = SweepEngine.from_configs(cfgs, data, nets)
        eng.run_block(eng.init_states(pinit), 0, ROUNDS)
        return eng

    eng = run_sweep()                     # warmup incl. compile
    n_compiled = int(eng._block._cache_size())
    sweep = _time(run_sweep, reps=3)

    def run_sequential():
        for c in cfgs:
            s = scenario_from_config(c, data, nets)
            e = RoundScanEngine(c, data, s.sufficient, s.eligible,
                                upload_mbps=s.upload_mbps,
                                packet_loss=s.packet_loss)
            e.run_block(e.init_state(pinit(jax.random.PRNGKey(c.seed))),
                        0, ROUNDS)

    seq = _time(run_sequential, reps=3)
    return {
        "scenarios": S, "rounds": ROUNDS, "n_clients": N_CLIENTS,
        "bursts": BURSTS, "loss_rates": RATES,
        "sweep_seconds": sweep, "sequential_seconds": seq,
        "sweep_scenarios_per_sec": S / sweep,
        "sequential_scenarios_per_sec": S / seq,
        "speedup_excl_compile": seq / sweep,
        "sweep_compiled_programs": n_compiled,
        "one_compile_for_grid": n_compiled == 1,
    }


def netsim_mask_and_grid():
    """Headline netsim numbers (emits BENCH_netsim.json)."""
    mask = _mask_gen_cell()
    grid = _burst_grid_cell()
    emit("BENCH_netsim",
         1e6 * grid["sweep_seconds"] / (grid["scenarios"] * ROUNDS),
         f"mask_gen {mask['device_vs_host']:.1f}x vs host numpy "
         f"({mask['device_masks_per_sec']:.0f} vs "
         f"{mask['host_masks_per_sec']:.0f} masks/s); burst grid "
         f"S{grid['scenarios']} {grid['speedup_excl_compile']:.1f}x vs "
         f"sequential ({grid['sweep_scenarios_per_sec']:.2f} scen/s, "
         f"one program: {grid['one_compile_for_grid']})")
    write_bench(
        "BENCH_netsim",
        config={"n_clients": N_CLIENTS, "rounds": ROUNDS,
                "bursts": BURSTS, "loss_rates": RATES},
        cells={"mask_gen": mask, "burst_grid": grid},
        honesty={
            "backend": jax.default_backend(),
            "note": "On CPU the device mask path is the XLA-compiled "
                    "jnp reference (no Pallas lowering), so mask_gen "
                    "measures vectorized JIT vs python-loop sampling "
                    "on the SAME silicon, and the burst-grid speedup "
                    "is per-round dispatch amortization, not extra "
                    "FLOPs. On TPU the mask path is the "
                    "kernels/netsim_mask Pallas kernel.",
        })


ALL = [netsim_mask_and_grid]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for fn in ALL:
        fn()
