"""Bring-up smoke of the federated round engine on one TPU chip.

Run from the root of a checkout:

    python chip_smoke.py [--seed N]

It drives the engine's main path through its normal entry points
(``FederatedServer`` / ``RoundScanEngine`` / ``SweepEngine``) with all
four Pallas kernels compiled, and checks every phase against the pure
``jax.numpy`` reference implementations, run on the same chip:

  paper         the paper's setting at full width: Synthetic(1,1),
                N = 100 clients, the 60-128-10 MLP (D = 9,098), q-FedAvg
                with TRA at 10% loss and error feedback, 20 rounds of a
                cohort of 10 through the round scan (``uplink_fused``);
  full          the full-feature program: Gilbert-Elliott channel with
                a deadline, async server, faults under screen + clip +
                trim, traced recovery including FEC, telemetry scalars
                (``netsim_mask``, ``fec_recover``, ``robust_agg``);
  sweep         an S = 4 loss-rate grid through ``SweepEngine`` — the
                batched ``uplink_fused`` grid — against S single runs;
  uplink_large  one ``uplink_round`` with error feedback at C = 64,
                D_up = 2^20 (256 MiB each for x, EF and EF-out).

One JSON line per phase reports its compile and run seconds (host
clock, ``block_until_ready``), the device's ``peak_bytes_in_use`` so
far, the resolved kernel implementations and every check with its
value and tolerance. These are bring-up readings, not benchmark
results. The last line is ``{"ok": true, "device": {...}}`` — printed
only when a TPU was found and every phase passed; anything else exits
non-zero. Each phase is a function of its sizes, so the tests run them
small on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from repro.core.async_agg import AsyncConfig  # noqa: E402
from repro.core.mlp import mlp_init  # noqa: E402
from repro.core.server import FederatedServer, FLConfig  # noqa: E402
from repro.core.sweep import SweepEngine  # noqa: E402
from repro.core.telemetry import TelemetryConfig  # noqa: E402
from repro.core.tra import TRAConfig  # noqa: E402
from repro.data.synthetic import generate_synthetic  # noqa: E402
from repro.kernels.fec_recover import ops as fec_ops  # noqa: E402
from repro.kernels.netsim_mask import ops as netsim_ops  # noqa: E402
from repro.kernels.robust_agg import ops as robust_ops  # noqa: E402
from repro.kernels.uplink_fused import ops as uplink_ops  # noqa: E402
from repro.netsim.config import NetSimConfig  # noqa: E402
from repro.netsim.faults import DefenseConfig, FaultConfig  # noqa: E402
from repro.netsim.recovery import RecoveryConfig  # noqa: E402
from repro.network.trace import sample_networks  # noqa: E402

IMPL_VARS = ("REPRO_UPLINK_IMPL", "REPRO_NETSIM_IMPL", "REPRO_ROBUST_IMPL",
             "REPRO_FEC_IMPL")

# Kernel-vs-reference tolerances. Both sides run on the chip, and XLA
# runs the reference's f32 matmuls (local training, the reference
# einsum) at its default one-pass bf16 precision, so two engine runs
# that differ only in how the uplink sums agree to a few bf16 ulps
# (2^-8 = 3.9e-3) after many rounds, not to f32 rounding.
TOL_ENGINE = 2e-2      # relative L2 distance of the final parameters
TOL_ACC = 0.02         # absolute difference of mean client accuracy
TOL_SWEEP = 2e-2       # sweep scenario vs its single run, relative L2
TOL_UPLINK = 1e-2      # max |kernel - f32 ref| over max |ref|, aggregate
TOL_SSQ = 1e-4         # relative, masked squared norms


def resolved_impls() -> dict:
    return {"uplink_fused": uplink_ops.resolved_impl(),
            "netsim_mask": netsim_ops.resolved_impl(),
            "robust_agg": robust_ops.resolved_impl(),
            "fec_recover": fec_ops.resolved_impl()}


@contextlib.contextmanager
def reference_impls():
    """Route all four kernels to their jnp references (the engine keys
    its compiled programs on the resolved impls, so this retraces)."""
    old = {k: os.environ.get(k) for k in IMPL_VARS}
    os.environ.update({k: "ref" for k in IMPL_VARS})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check(value, tol, ok=None) -> dict:
    return {"value": value, "tol": tol,
            "ok": bool(value <= tol) if ok is None else bool(ok)}


def flat(params) -> np.ndarray:
    return np.asarray(ravel_pytree(params)[0])


def kernel_calls(hlo: str) -> int:
    """Compiled Pallas kernels in a program's HLO text."""
    return hlo.count('custom_call_target="tpu_custom_call"')


def paper_setup(n_clients: int, seed: int):
    rng = np.random.default_rng(seed)
    data = generate_synthetic(rng, n_clients=n_clients, alpha=1.0,
                              beta=1.0)
    return data, sample_networks(rng, n_clients)


def run_server(cfg: FLConfig, data, nets) -> dict:
    """One ``FederatedServer.run`` as a user calls it, timed: an AOT
    compile of its round-block program (whose HLO shows the kernels),
    the first ``run()``, then the same rounds again from the same start
    on the warm program."""
    srv = FederatedServer(cfg, data, nets)
    eng = srv.engine
    ts = jnp.arange(cfg.n_rounds, dtype=jnp.int32)
    t0 = time.perf_counter()
    hlo = eng._block.lower(eng.ctx, srv._state, ts).compile().as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.run()
    jax.block_until_ready(srv.params)
    first_s = time.perf_counter() - t0
    state = eng.init_state(mlp_init(jax.random.PRNGKey(cfg.seed)))
    t0 = time.perf_counter()
    state, logs = eng.run_block(state, 0, cfg.n_rounds)
    jax.block_until_ready(state.params)
    run_s = time.perf_counter() - t0
    losses = [r.train_loss for r in srv.history]
    return {"srv": srv, "logs": logs, "compile_s": compile_s,
            "first_run_s": first_s, "run_s": run_s,
            "kernel_calls": kernel_calls(hlo),
            "params": flat(srv.params), "losses": losses,
            "acc": float(srv.evaluate().average)}


def compare_engine_runs(k: dict, r: dict) -> dict:
    return {"params_rel_l2": check(rel_l2(k["params"], r["params"]),
                                   TOL_ENGINE),
            "acc_diff": check(abs(k["acc"] - r["acc"]), TOL_ACC)}


def timings(run: dict) -> dict:
    return {key: run[key] for key in ("compile_s", "first_run_s", "run_s")}


def paper_config(n_rounds, cohort, local_steps, batch_size, seed,
                 loss_rate=0.1) -> FLConfig:
    return FLConfig(algo="qfedavg", n_rounds=n_rounds,
                    clients_per_round=cohort, local_steps=local_steps,
                    batch_size=batch_size, eval_every=n_rounds,
                    error_feedback=True, seed=seed,
                    tra=TRAConfig(enabled=True, loss_rate=loss_rate))


def phase_paper(n_clients=100, cohort=10, n_rounds=20, local_steps=20,
                batch_size=32, seed=0) -> dict:
    data, nets = paper_setup(n_clients, seed)
    cfg = paper_config(n_rounds, cohort, local_steps, batch_size, seed)
    k = run_server(cfg, data, nets)
    with reference_impls():
        r = run_server(cfg, data, nets)
    loss = k["losses"]
    checks = compare_engine_runs(k, r)
    checks["loss_finite_and_falls"] = check(
        loss[-1], loss[0], ok=np.isfinite(loss).all() and loss[-1] < loss[0])
    return {"phase": "paper", **timings(k), "ref_timings": timings(r),
            "kernel_calls": k["kernel_calls"],
            "acc": {"kernel": k["acc"], "ref": r["acc"]},
            "loss_first_last": [loss[0], loss[-1]], "checks": checks}


def full_config(n_rounds, cohort, local_steps, batch_size, seed
                ) -> FLConfig:
    return FLConfig(
        algo="fedavg", n_rounds=n_rounds, clients_per_round=cohort,
        local_steps=local_steps, batch_size=batch_size,
        eval_every=n_rounds, error_feedback=True, seed=seed,
        tra=TRAConfig(enabled=True, loss_rate=0.2),
        netsim=NetSimConfig(channel="gilbert_elliott", burst_len=8.0,
                            deadline=True, deadline_s=60.0),
        srv=AsyncConfig(mode="async", buffer_k=4),
        faults=FaultConfig(enabled=True, corrupt_rate=0.05,
                           corrupt_scale=5.0, fail_rate=0.05),
        defense=DefenseConfig(screen=True, clip=True, clip_norm=20.0,
                              trim=True, trim_k=1),
        recovery=RecoveryConfig(traced=True, policy="fec"),
        telemetry=TelemetryConfig(level="scalars"))


def phase_full(n_clients=100, cohort=10, n_rounds=5, local_steps=20,
               batch_size=32, seed=0) -> dict:
    data, nets = paper_setup(n_clients, seed)
    cfg = full_config(n_rounds, cohort, local_steps, batch_size, seed)
    k = run_server(cfg, data, nets)
    with reference_impls():
        r = run_server(cfg, data, nets)
    tele = {name: v for name, v in k["logs"].items()
            if name.startswith("tele/")}
    checks = compare_engine_runs(k, r)
    checks["finite"] = check(
        0, 0, ok=np.isfinite(k["params"]).all()
        and all(np.isfinite(v).all() for v in tele.values()))
    checks["telemetry_scalars"] = check(len(tele), 0, ok=len(tele) > 0)
    return {"phase": "full", **timings(k), "ref_timings": timings(r),
            "kernel_calls": k["kernel_calls"],
            "acc": {"kernel": k["acc"], "ref": r["acc"]},
            "fec_recovered": float(np.mean(k["logs"]["tele/fec_recovered"]))
            if "tele/fec_recovered" in k["logs"] else None,
            "checks": checks}


def phase_sweep(n_clients=100, cohort=10, n_rounds=10, local_steps=20,
                batch_size=32, seed=0,
                loss_rates=(0.0, 0.1, 0.2, 0.3)) -> dict:
    data, nets = paper_setup(n_clients, seed)
    cfgs = [paper_config(n_rounds, cohort, local_steps, batch_size, seed,
                         loss_rate=lr) for lr in loss_rates]
    eng = SweepEngine.from_configs(cfgs, data, nets)
    states = eng.init_states()
    ts = jnp.arange(n_rounds, dtype=jnp.int32)
    t0 = time.perf_counter()
    hlo = eng._block.lower(eng.ctx, states, ts).compile().as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    states, logs = eng.run()
    jax.block_until_ready(states.params)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again, _ = eng.run()
    jax.block_until_ready(again.params)
    run_s = time.perf_counter() - t0
    checks = {}
    for s, cfg in enumerate(cfgs):
        single = run_server(cfg, data, nets)
        checks[f"scenario{s}_rel_l2"] = check(
            rel_l2(flat(jax.tree.map(lambda x: x[s], states.params)),
                   single["params"]), TOL_SWEEP)
    return {"phase": "sweep", "compile_s": compile_s,
            "first_run_s": first_s, "run_s": run_s,
            "kernel_calls": kernel_calls(hlo),
            "loss_rates": list(loss_rates),
            "final_loss": [float(v) for v in logs["loss"][:, -1]],
            "checks": checks}


def phase_uplink_large(cohort=64, d_up=1 << 20, seed=0,
                       mode="group_rate") -> dict:
    F = 256
    P = -(-d_up // F)
    kx, ke, km, kw, ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xp = jnp.pad(jax.random.normal(kx, (cohort, d_up), jnp.float32),
                 ((0, 0), (0, P * F - d_up))).reshape(cohort, P, F)
    ef = 0.1 * jax.random.normal(ke, (cohort, d_up), jnp.float32)
    mask = (jax.random.uniform(km, (cohort, P)) >= 0.1).astype(jnp.float32)
    w = jax.random.uniform(kw, (cohort,), minval=0.1, maxval=1.0)
    suff = (jax.random.uniform(ks, (cohort,)) < 0.3).astype(jnp.float32)

    def step(xp, mask, w, ef, suff):
        return uplink_ops.uplink_round(
            xp, mask, w, mode=mode, d_up=d_up, ef_rows=ef, sufficient=suff,
            loss_rate=jnp.float32(0.1), want_ssq=True)

    args = (xp, mask, w, ef, suff)
    t0 = time.perf_counter()
    fn = jax.jit(step).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    run_s = time.perf_counter() - t0
    with reference_impls(), jax.default_matmul_precision("float32"):
        ref = jax.block_until_ready(jax.jit(step)(*args))
    agg_err = float(jnp.max(jnp.abs(out[0] - ref[0]))
                    / jnp.max(jnp.abs(ref[0])))
    ssq_err = float(jnp.max(jnp.abs(out[2] - ref[2]) / ref[2]))
    ef_equal = bool(jnp.array_equal(out[1], ref[1]))
    return {"phase": "uplink_large", "compile_s": compile_s,
            "first_run_s": first_s, "run_s": run_s,
            "shape": {"C": cohort, "P": P, "F": F, "d_up": d_up},
            "kernel_calls": kernel_calls(fn.as_text()),
            "checks": {"agg_max_err": check(agg_err, TOL_UPLINK),
                       "ssq_rel_err": check(ssq_err, TOL_SSQ),
                       "ef_out_bitwise": check(0, 0, ok=ef_equal)}}


PHASES = {"paper": phase_paper, "full": phase_full, "sweep": phase_sweep,
          "uplink_large": phase_uplink_large}


def emit(obj) -> None:
    print(json.dumps(obj, default=float), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device})", file=sys.stderr)
        return 2
    emit({"device": device})
    overrides = {k: os.environ[k] for k in IMPL_VARS if k in os.environ}
    impls = resolved_impls()
    if overrides or set(impls.values()) != {"kernel"}:
        print(f"chip_smoke: kernels not in use: overrides={overrides} "
              f"resolved={impls}", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache
    emit({"compile_cache": enable_compile_cache()})
    ok = True
    for name, phase in PHASES.items():
        try:
            res = phase(seed=args.seed)
        except Exception as e:  # noqa: BLE001 — report, then fail
            emit({"phase": name, "error": f"{type(e).__name__}: {e}"})
            ok = False
            continue
        res["peak_bytes_in_use"] = peak_bytes()
        res["impls"] = resolved_impls()
        passed = res["kernel_calls"] > 0 and all(
            c["ok"] for c in res["checks"].values())
        res["ok"] = passed
        emit(res)
        ok = ok and passed
    if not ok:
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
