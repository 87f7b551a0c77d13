"""Federated server orchestration (thread Server of Algorithm 1).

Execution is delegated to the device-resident round-scan engine
(`core/engine.py`): the whole round — on-device client selection,
vmapped local training over the cohort, simulated lossy uploads (TRA)
or reliable uploads (threshold mode), debiased aggregation fused with
the error-feedback update into one pass over the uploads
(`kernels/uplink_fused`) — is one compiled step, and ``run`` scans
*blocks* of rounds in a single device program, flushing loss logs at
evaluation boundaries. ``run_round``
executes the same step once per call (the per-round reference path),
so the two paths are fixed-seed equivalent (tests/test_engine.py).

Selection policies (the paper's comparison axis):
  "all"        every client eligible (TRA's fair selection)
  "ratio"      top-X% of clients by upload speed (the paper's 70/80/90%
               "eligible ratio" threshold settings)
  "threshold"  speed >= threshold_mbps (OpenMined-style 2 Mbps)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import client_updates as cu
from repro.core import telemetry as tele_mod
from repro.core import tra as tra_mod
from repro.core.async_agg import AsyncConfig
from repro.core.engine import RoundScanEngine, _static_key, tree_bytes
from repro.core.selection import SelectionConfig
from repro.core.telemetry import TelemetryConfig
from repro.utils.events import EventWriter, fingerprint_of
from repro.core.fairness import FairnessReport, fairness_report
from repro.core.mlp import MLPModel
from repro.core.sweep import SweepEngine
from repro.core.tra import TRAConfig
from repro.core import lossbudget as bud_mod
from repro.netsim import recovery as rec_mod
from repro.netsim.config import NetSimConfig
from repro.netsim.faults import DefenseConfig, FaultConfig
from repro.data.synthetic import (FederatedDataset, padded_eval_set,
                                  sample_batches)
from repro.network.trace import (ClientNetworks, eligible_by_ratio,
                                 eligible_by_threshold,
                                 eligible_mask_device, sample_networks)


@dataclasses.dataclass
class FLConfig:
    algo: str = "fedavg"              # fedavg|qfedavg|pfedme|perfedavg|afl
    n_rounds: int = 100
    clients_per_round: int = 10
    local_steps: int = 20
    batch_size: int = 32
    lr: float = 0.1
    selection: str = "all"            # all|ratio|threshold
    eligible_ratio: float = 1.0       # for selection="ratio"
    # score-based cohort sampling OVER the eligible set (the traced
    # selection-policy family, core/selection.py): uniform (default,
    # bit-identical to the pre-policy engine) | bandwidth_threshold
    # (the paper's biased baseline) | gradient_norm | loss_aware |
    # netsim_state. ``selection`` above gates *eligibility*; ``sel``
    # weights the draw among the eligible.
    sel: SelectionConfig = dataclasses.field(
        default_factory=SelectionConfig)
    tra: TRAConfig = dataclasses.field(default_factory=TRAConfig)
    # stateful network simulator (repro/netsim): Gilbert-Elliott bursty
    # loss, AR(1) time-varying bandwidth, deadline delivery. The default
    # (channel="iid", models off) is the pre-netsim engine bit-for-bit.
    netsim: NetSimConfig = dataclasses.field(default_factory=NetSimConfig)
    # server aggregation mode (core/async_agg.py): sync (default,
    # bitwise the pre-async engine) | semi_sync (deadline + staleness-
    # discounted grace window) | async (K-slot arrival buffer; late
    # uploads land staleness-discounted in the round they arrive).
    # Requires netsim.deadline=True for the non-sync modes.
    srv: AsyncConfig = dataclasses.field(default_factory=AsyncConfig)
    # uplink fault injection (repro/netsim/faults.py): corruption the
    # transport DELIVERS — per-packet Gaussian/bit-flip damage,
    # per-client NaN device failures, sign flips, stale echoes. The
    # default (enabled=False) is the pre-faults engine bit-for-bit.
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    # robust-aggregation defenses (kernels/robust_agg): finite-screen
    # quarantine, per-client norm clipping, coordinate-wise trimmed
    # mean. Gates are traced; requires faults.enabled (the defended
    # uplink path is only compiled with the fault model).
    defense: DefenseConfig = dataclasses.field(
        default_factory=DefenseConfig)
    # device-resident telemetry (core/telemetry.py): per-round scalars
    # and per-client aggregates accumulated inside the scan and flushed
    # as typed RoundRecords (utils/events.py). The default
    # (level="off") compiles the subsystem out and is bit-identical to
    # the pre-telemetry engine. STATIC: the level cannot vary across a
    # sweep (it changes the compiled program).
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig)
    # uplink recovery-policy family (repro/netsim/recovery.py):
    # one_shot (default, the paper's TRA — bit-identical to the
    # pre-recovery engine) | fec (XOR parity per group of G, any single
    # loss per group repaired on device) | arq (bounded retransmits
    # with traced retries/backoff; extra airtime feeds the deadline
    # machinery). recovery.traced compiles the whole family into one
    # program. Non-one_shot policies require tra.enabled.
    recovery: "rec_mod.RecoveryConfig" = dataclasses.field(
        default_factory=lambda: rec_mod.RecoveryConfig())
    # adaptive loss-budget controller (core/lossbudget.py): per-client
    # closed loop escalating one_shot -> fec -> arq when the realized
    # loss EMA exceeds the budget or update norms diverge. Requires
    # recovery.traced (the controller picks per-client policies from
    # the traced family).
    lossbudget: "bud_mod.LossBudgetConfig" = dataclasses.field(
        default_factory=lambda: bud_mod.LossBudgetConfig())
    # algorithm hyper-parameters (paper / source-code defaults)
    q: float = 1.0                    # q-FedAvg fairness exponent
    # q-FedAvg Lipschitz estimate. Li et al. use 1/lr; with 10 local steps
    # our pseudo-gradients are ~10x larger, over-damping h — L=1.0
    # restores the paper's convergence/fairness behaviour (see
    # docs/EXPERIMENTS.md).
    lipschitz: float = 1.0
    pfedme_lam: float = 15.0
    pfedme_K: int = 5
    pfedme_eta: float = 0.05
    pfedme_beta: float = 1.0          # server mixing
    perfed_alpha: float = 0.01
    perfed_beta: float = 0.1
    afl_lr_lambda: float = 0.1
    # EF-TRA (beyond-paper, DESIGN §7): clients accumulate their DROPPED
    # coordinates locally and re-inject them into the next upload —
    # removes the debias variance penalty at +1 update buffer per client.
    error_feedback: bool = False
    seed: int = 0
    eval_every: int = 10
    # "scan" compiles blocks of rounds into one lax.scan program;
    # "per_round" dispatches the same compiled step once per round
    # (reference path, also what run_round uses).
    engine: str = "scan"
    # the client model (core/client_model.py): what a client trains
    # and uploads, and what stays frozen. STATIC program structure.
    model: Any = dataclasses.field(default_factory=MLPModel)

    def hyper(self) -> Dict[str, float]:
        return {
            "lr": self.lr, "lipschitz": self.lipschitz,
            "lam": self.pfedme_lam, "K": self.pfedme_K,
            "eta": self.pfedme_eta, "alpha": self.perfed_alpha,
            "beta_maml": self.perfed_beta,
        }


@dataclasses.dataclass
class RoundLog:
    round: int
    train_loss: float
    report: Optional[FairnessReport] = None
    personalized: Optional[FairnessReport] = None


class FederatedServer:
    """Runs LT-FL on ``cfg.model``, the client model: the paper's MLP on
    Synthetic rows by default, or federated LoRA on a frozen Zamba2 base
    over per-client token sequences (``core/client_model.py``). ``init``
    = (trainable, frozen) replaces the model's own ``init`` from
    ``cfg.seed``. The mesh-sharded host loop of launch/fl_train.py is a
    separate path."""

    def __init__(self, cfg: FLConfig, data: FederatedDataset,
                 nets: Optional[ClientNetworks] = None, init=None):
        if cfg.engine not in ("scan", "per_round"):
            raise ValueError(f"unknown engine {cfg.engine!r}")
        self.cfg = cfg
        self.data = data
        self.rng = np.random.default_rng(cfg.seed)
        self.nets = nets if nets is not None else sample_networks(
            self.rng, data.n_clients)
        self.sufficient = tra_mod.sufficiency_report(
            self.nets, cfg.tra.threshold_mbps)
        self.eval_X, self.eval_Y, self.eval_W = padded_eval_set(data)
        elig = eligible_mask_device(
            jnp.asarray(self.nets.upload_mbps), cfg.selection,
            eligible_ratio=cfg.eligible_ratio,
            threshold_mbps=cfg.tra.threshold_mbps)
        model = cfg.model
        trainable, self.frozen = model.init(jax.random.PRNGKey(cfg.seed)) \
            if init is None else init
        self.engine = RoundScanEngine(cfg, data, self.sufficient,
                                      np.asarray(elig),
                                      upload_mbps=self.nets.upload_mbps,
                                      packet_loss=self.nets.packet_loss,
                                      frozen=self.frozen)
        self._state = self.engine.init_state(trainable)
        self._eval_fn = tele_mod.TimedProgram(
            jax.jit(jax.vmap(model.eval_metric,
                             in_axes=(None, None, 0, 0, 0))),
            "eval", tele_mod.REGISTRY.record_lookup(
                "eval", (f"vmap({model.name}.eval_metric)",
                         self.eval_X.shape), hit=False), "eval")
        self._eval_fn.count(resident=tree_bytes(self.frozen))
        # tokens an eval scores: every held-out sequence's next tokens
        self._eval_tokens = int(self.eval_W.sum()) \
            * (self.eval_X.shape[-1] - 1) if model.seq_len else 0
        self.history: List[RoundLog] = []

    # -- device-resident state, host views ----------------------------------
    @property
    def params(self):
        return self._state.params

    @property
    def _dim(self) -> int:
        from jax.flatten_util import ravel_pytree
        return ravel_pytree(self.params)[0].shape[0]

    @property
    def _ef_mem(self) -> np.ndarray:
        return np.asarray(self._state.ef_mem)

    @property
    def _c_global(self) -> np.ndarray:
        return np.asarray(self._state.c_global)

    @property
    def _c_i(self) -> np.ndarray:
        return np.asarray(self._state.c_i)

    @property
    def _lambda(self) -> np.ndarray:
        return np.asarray(self._state.lam)  # AFL state

    # -- selection ---------------------------------------------------------
    def eligible_mask(self) -> np.ndarray:
        cfg = self.cfg
        if cfg.selection == "all":
            return np.ones(self.data.n_clients, bool)
        if cfg.selection == "ratio":
            return eligible_by_ratio(self.nets, cfg.eligible_ratio)
        if cfg.selection == "threshold":
            return eligible_by_threshold(self.nets, cfg.tra.threshold_mbps)
        raise ValueError(cfg.selection)

    def select(self) -> np.ndarray:
        """Host-side reference sampler (the engine selects on device)."""
        elig = np.flatnonzero(self.eligible_mask())
        n = min(self.cfg.clients_per_round, len(elig))
        return self.rng.choice(elig, n, replace=False)

    # -- public API ---------------------------------------------------------
    def _open_events(self, events):
        """(writer, owned): pass-through for an EventWriter, open+stamp
        for a path. Owned writers are closed by the caller's finally."""
        if events is None or isinstance(events, EventWriter):
            return events, False
        cfg = self.cfg
        return EventWriter(
            events,
            config_fingerprint=fingerprint_of(_static_key(cfg)),
            meta={"n_clients": self.data.n_clients,
                  "n_rounds": cfg.n_rounds, "algo": cfg.algo,
                  "engine": cfg.engine,
                  "telemetry_level": cfg.telemetry.level}), True

    def run_round(self, t: int) -> RoundLog:
        cfg = self.cfg
        self._state, ys = self.engine.run_single(self._state, t)
        self._last_ys = ys
        log = RoundLog(t, float(ys["loss"]))
        if (t + 1) % cfg.eval_every == 0 or t == cfg.n_rounds - 1:
            log.report = self.evaluate()
            if cfg.algo in ("pfedme", "perfedavg"):
                log.personalized = self.evaluate_personalized()
        self.history.append(log)
        return log

    def run(self, events=None) -> List[RoundLog]:
        """Run all rounds. ``events`` — None, a JSONL path, or an open
        ``EventWriter`` — streams typed per-round telemetry records
        (plus final client aggregates at level="full" and the
        program-timing ledger) as blocks flush."""
        cfg = self.cfg
        writer, own = self._open_events(events)
        try:
            if cfg.engine == "per_round":
                for t in range(cfg.n_rounds):
                    self.run_round(t)
                    if writer is not None:
                        logs1 = {k: np.asarray(v)[None]
                                 for k, v in self._last_ys.items()}
                        for rec in tele_mod.records_from_logs(
                                logs1, t0=t):
                            writer.write_round(rec)
            else:
                # scanned blocks, cut at evaluation boundaries
                t = 0
                while t < cfg.n_rounds:
                    t1 = min((t // cfg.eval_every + 1) * cfg.eval_every,
                             cfg.n_rounds)
                    self._state, logs = self.engine.run_block(
                        self._state, t, t1 - t)
                    for i, loss in enumerate(logs["loss"]):
                        self.history.append(RoundLog(t + i, float(loss)))
                    if writer is not None:
                        for rec in tele_mod.records_from_logs(
                                logs, t0=t):
                            writer.write_round(rec)
                    last = t1 - 1
                    if t1 % cfg.eval_every == 0 or last == cfg.n_rounds - 1:
                        self.history[-1].report = self.evaluate()
                        if cfg.algo in ("pfedme", "perfedavg"):
                            self.history[-1].personalized = \
                                self.evaluate_personalized()
                    t = t1
            if writer is not None:
                if cfg.telemetry.level == "full":
                    writer.write("client_stats", {
                        "scenario": 0,
                        **tele_mod.final_client_stats(self._state.tele)})
                writer.write_program_stats(tele_mod.REGISTRY.stats())
        finally:
            if own and writer is not None:
                writer.close()
        return self.history

    # -- evaluation ----------------------------------------------------------
    def evaluate(self, params=None) -> FairnessReport:
        p = self.params if params is None else params
        with tele_mod.Span("fl.eval"):
            # one put: the eval set goes up in one runtime call, as
            # uncommitted arrays of its own dtypes, so the eval program
            # compiles once
            with tele_mod.Span("fl.eval.upload"):
                X, Y, W = jax.device_put(
                    (self.eval_X, self.eval_Y, self.eval_W))
            out = self._eval_fn(p, self.frozen, X, Y, W)
            # one fetch, as in RoundScanEngine.run_block: every output's
            # copy is queued behind the program before the host waits
            with tele_mod.Span("fl.eval.copy"):
                for v in out:
                    v.copy_to_host_async()
                acc, correct, n = (np.asarray(v) for v in out)
            self._eval_fn.count(
                h2d=self.eval_X.nbytes + self.eval_Y.nbytes
                + self.eval_W.nbytes,
                d2h=acc.nbytes + correct.nbytes + n.nbytes,
                arrays=len(out), tokens=self._eval_tokens)
            with tele_mod.Span("fl.eval.report"):
                return fairness_report(acc, n, correct)

    def evaluate_personalized(self) -> FairnessReport:
        """Adapt the global model per client, then evaluate (pFedMe 'P' /
        Per-FedAvg test-time adaptation)."""
        cfg = self.cfg
        X, Y = sample_batches(self.rng, self.data,
                              np.arange(self.data.n_clients),
                              cfg.pfedme_K, cfg.batch_size)
        hyper = cfg.hyper()
        model, frozen = cfg.model, self.frozen
        personalize = cu.pfedme_personalize if cfg.algo == "pfedme" \
            else cu.perfedavg_personalize
        fn = jax.jit(jax.vmap(
            lambda p, x, y, h: personalize(
                p, x, y, h, lambda p_, x_, y_: model.loss(p_, frozen, x_,
                                                          y_)),
            in_axes=(None, 0, 0, None)))
        per_params = fn(self.params, jnp.asarray(X), jnp.asarray(Y), hyper)
        eval_fn = jax.jit(jax.vmap(model.eval_metric,
                                   in_axes=(0, None, 0, 0, 0)))
        acc, correct, n = eval_fn(per_params, frozen,
                                  jnp.asarray(self.eval_X),
                                  jnp.asarray(self.eval_Y),
                                  jnp.asarray(self.eval_W))
        return fairness_report(np.asarray(acc), np.asarray(n),
                               np.asarray(correct))


# ---------------------------------------------------------------------------
# grid execution: S scenario configs -> one vmap(scan) program
# ---------------------------------------------------------------------------
def _stacked_eval_sets(datas: Sequence[FederatedDataset]):
    """Per-scenario padded eval sets, re-padded to a common length and
    stacked behind the scenario axis: (S, N, M), mask-weighted so the
    cross-scenario padding never scores."""
    sets = [padded_eval_set(d) for d in datas]
    M = max(x.shape[1] for x, _, _ in sets)

    def _pad(a):
        return np.pad(a, ((0, 0), (0, M - a.shape[1]))
                      + ((0, 0),) * (a.ndim - 2))

    X = np.stack([_pad(x) for x, _, _ in sets])
    Y = np.stack([_pad(y) for _, y, _ in sets])
    W = np.stack([_pad(w) for _, _, w in sets])
    return jnp.asarray(X), jnp.asarray(Y), jnp.asarray(W)


def run_grid(cfgs: Sequence[FLConfig], datas, nets=None, events=None
             ) -> List[List[RoundLog]]:
    """Run a grid of same-shaped scenario configs as ONE compiled
    vmap(scan) program (core/sweep.SweepEngine) and demux per-scenario
    histories on flush.

    Mirrors ``FederatedServer.run`` for each scenario: same block
    boundaries, same eval schedule, fairness reports computed (vmapped
    over the scenario axis) at eval boundaries. Per-scenario histories
    are bit-identical to S independent servers (tests/test_sweep.py).
    Personalized (pFedMe / Per-FedAvg) evaluation is not offered on the
    grid path — run those cells through ``FederatedServer`` when the
    personalized report is needed.

    ``datas``/``nets`` follow ``SweepEngine.from_configs`` broadcasting:
    one shared value, a length-S sequence, or None (nets only) to sample
    from each scenario's seed.

    ``events`` — None, a JSONL path, or an open ``EventWriter`` —
    streams per-scenario telemetry records (scenario-major within each
    block) plus final per-client aggregates (level="full") and the
    program-timing ledger.
    """
    cfgs = list(cfgs)
    engine = SweepEngine.from_configs(cfgs, datas, nets)
    cfg = engine.cfg
    S = engine.n_scenarios
    if events is None or isinstance(events, EventWriter):
        writer, own = events, False
    else:
        writer, own = EventWriter(
            events,
            config_fingerprint=fingerprint_of(_static_key(cfg)),
            meta={"n_scenarios": S, "n_rounds": cfg.n_rounds,
                  "algo": cfg.algo, "engine": "sweep",
                  "telemetry_level": cfg.telemetry.level}), True
    X, Y, W = _stacked_eval_sets([s.data for s in engine.scenarios])
    eval_fn = jax.jit(jax.vmap(jax.vmap(
        lambda p, x, y, w: cfg.model.eval_metric(p, None, x, y, w),
        in_axes=(None, 0, 0, 0))))
    states = engine.init_states()
    histories: List[List[RoundLog]] = [[] for _ in range(S)]
    try:
        t = 0
        while t < cfg.n_rounds:
            t1 = min((t // cfg.eval_every + 1) * cfg.eval_every,
                     cfg.n_rounds)
            states, logs = engine.run_block(states, t, t1 - t)
            for s in range(S):
                for i in range(t1 - t):
                    histories[s].append(
                        RoundLog(t + i, float(logs["loss"][s, i])))
            if writer is not None:
                for rec in tele_mod.records_from_logs(logs, t0=t):
                    writer.write_round(rec)
            last = t1 - 1
            if t1 % cfg.eval_every == 0 or last == cfg.n_rounds - 1:
                acc, correct, n = eval_fn(states.params, X, Y, W)
                acc, correct, n = (np.asarray(acc), np.asarray(correct),
                                   np.asarray(n))
                for s in range(S):
                    histories[s][-1].report = fairness_report(
                        acc[s], n[s], correct[s])
            t = t1
        if writer is not None:
            if cfg.telemetry.level == "full":
                stats = tele_mod.final_client_stats(states.tele)
                for s in range(S):
                    writer.write("client_stats", {
                        "scenario": s,
                        **{k: v[s] for k, v in stats.items()}})
            writer.write_program_stats(tele_mod.REGISTRY.stats())
    finally:
        if own and writer is not None:
            writer.close()
    return histories
