"""Vectorized multi-scenario sweep engine: the whole paper grid as ONE
compiled ``vmap(scan)`` program.

A *scenario* is everything that may vary without changing program
structure: the PRNG seed, the TRA loss rate, the eligibility and
sufficiency masks (selection policy applied to that scenario's network
draw), and the dataset draw (alpha/beta heterogeneity re-draws). The
paper's result grids — loss rate x debias mode x algorithm x seeds —
decompose into groups of such scenarios per static configuration.

``SweepEngine`` stacks S scenario instances behind a leading scenario
axis: ``ScenarioCtx`` fields become (S, ...) arrays, per-scenario
``EngineState`` is tree-stacked, and the staged data is rectangular
(S, N, M, D) (``data/synthetic.stage_scenarios_on_device``). One
``jax.vmap`` over the SAME round step that ``RoundScanEngine`` jits
runs every scenario's round at once, and one ``lax.scan`` runs all
rounds — so an entire grid is one XLA program, compiled once,
dispatched once per block. Per-scenario (loss, ids) histories come
back stacked and are demuxed on flush; they are bit-identical to S
independent ``RoundScanEngine`` runs with the same seeds/configs
(tests/test_sweep.py, CI smoke).

Static structure — algorithm, debias mode, cohort size, local steps,
batch size, TRA on/off, error feedback, round/eval schedule, learning
hyper-parameters — must be shared across a sweep; ``from_configs``
validates that and raises on a mixed grid (split such a grid into one
sweep per static signature).

The stacked ``EngineState`` is donated into the sweep jit, so the
(S, N, D_up) error-feedback and SCAFFOLD buffers are updated in place
rather than copied every block.

On TPU the round step's fused uplink (`kernels/uplink_fused`) rides
this vmap through its ``custom_vmap`` rule: the S scenarios' uplink
becomes ONE scenario-batched megakernel launch over the (S, C, P, F)
uploads, bit-identical to S single-scenario calls
(tests/test_uplink_fused.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import async_agg as async_mod
from repro.core import lossbudget as bud_mod
from repro.core import selection as sel_mod
from repro.core import telemetry as tele_mod
from repro.core import tra as tra_mod
from repro.core.async_agg import AsyncConfig
from repro.core.engine import (ENGINE_ALGOS, SWEEP_VARYING_BUD_FIELDS,
                               SWEEP_VARYING_DEF_FIELDS,
                               SWEEP_VARYING_FAULT_FIELDS,
                               SWEEP_VARYING_FIELDS,
                               SWEEP_VARYING_NETSIM_FIELDS,
                               SWEEP_VARYING_REC_FIELDS,
                               SWEEP_VARYING_SEL_FIELDS,
                               SWEEP_VARYING_SRV_FIELDS,
                               SWEEP_VARYING_TRA_FIELDS, EngineState,
                               ScenarioCtx, _static_key,
                               init_engine_state, make_round_step,
                               static_signature)
from repro.core.lossbudget import LossBudgetConfig
from repro.core.mlp import mlp_init
from repro.core.selection import SelectionConfig
from repro.netsim import faults as faults_mod
from repro.netsim import recovery as rec_mod
from repro.netsim.config import NetSimConfig
from repro.netsim.faults import DefenseConfig, FaultConfig
from repro.netsim.recovery import RecoveryConfig
from repro.data.synthetic import (DeviceDataset, FederatedDataset,
                                  stage_on_device,
                                  stage_scenarios_on_device)
from repro.network.trace import (eligible_mask_device, log_upload_speeds,
                                 sample_networks,
                                 stage_network_scenarios)

# sweep-program cache, mirroring engine._STEP_CACHE: one compiled
# vmap(scan) program per (static config, cohort, shared-vs-stacked
# data); grids of any size S reuse it (jit re-specialises per shape).
_SWEEP_CACHE: Dict[Any, Any] = {}


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One cell of a paper grid (host-side description)."""
    seed: int
    loss_rate: float
    sufficient: np.ndarray        # (N,) 0/1 sufficiency reports
    eligible: np.ndarray          # (N,) bool selection mask
    data: FederatedDataset        # this scenario's dataset draw
    # netsim scenario axis: this cell's channel/bandwidth/deadline
    # knobs (None -> the sweep config's cfg.netsim; static model flags
    # must agree across a sweep, traced knobs may vary per cell)
    netsim: Optional[NetSimConfig] = None
    # selection-policy scenario axis (None -> cfg.sel): threshold /
    # temperature / explore may vary per cell; the policy NAME may vary
    # only when the sweep config is traced (cfg.sel.traced — the
    # one-hot rides ScenarioCtx.sel_policy)
    sel: Optional[SelectionConfig] = None
    # server-mode scenario axis (None -> cfg.srv): staleness_alpha /
    # grace_s may vary per cell; the mode NAME may vary only when the
    # sweep config is traced (cfg.srv.traced — the one-hot rides
    # ScenarioCtx.srv_mode); traced flag and buffer_k must agree
    srv: Optional[AsyncConfig] = None
    # fault-model scenario axes (None -> cfg.faults / cfg.defense):
    # the injection RATES and the defense GATES may vary per cell —
    # a fault-rate x defense grid is ONE program; faults.enabled and
    # defense.trim_k are static and must agree across the sweep
    faults: Optional[FaultConfig] = None
    defense: Optional[DefenseConfig] = None
    # recovery-policy scenario axis (None -> cfg.recovery): retries /
    # backoff may vary per cell; the policy NAME may vary only when the
    # sweep config is traced (cfg.recovery.traced — the one-hot rides
    # ScenarioCtx.rec_policy); traced flag and group must agree
    recovery: Optional[RecoveryConfig] = None
    # loss-budget scenario axis (None -> cfg.lossbudget): budget / ema /
    # div_gate may vary per cell; enabled is static and must agree
    lossbudget: Optional[LossBudgetConfig] = None
    # per-client trace draws, needed when tra.per_client_loss or a
    # netsim bandwidth/deadline model is on
    packet_loss: Optional[np.ndarray] = None   # (N,) drop rates
    upload_mbps: Optional[np.ndarray] = None   # (N,) speeds


def scenario_from_config(cfg, data: FederatedDataset,
                         nets=None) -> Scenario:
    """Derive a Scenario exactly the way ``FederatedServer`` derives its
    engine inputs (same network sampling from the scenario seed, same
    sufficiency report and eligibility policy), so sweep cells match
    single-server runs bit-for-bit."""
    rng = np.random.default_rng(cfg.seed)
    if nets is None:
        nets = sample_networks(rng, data.n_clients)
    sufficient = tra_mod.sufficiency_report(nets, cfg.tra.threshold_mbps)
    eligible = np.asarray(eligible_mask_device(
        jnp.asarray(nets.upload_mbps), cfg.selection,
        eligible_ratio=cfg.eligible_ratio,
        threshold_mbps=cfg.tra.threshold_mbps))
    return Scenario(seed=cfg.seed, loss_rate=cfg.tra.loss_rate,
                    sufficient=sufficient, eligible=eligible, data=data,
                    netsim=cfg.netsim, sel=cfg.sel, srv=cfg.srv,
                    faults=cfg.faults, defense=cfg.defense,
                    recovery=cfg.recovery, lossbudget=cfg.lossbudget,
                    packet_loss=nets.packet_loss,
                    upload_mbps=nets.upload_mbps)


class SweepEngine:
    """vmap(scan) executor for S same-shaped scenarios.

    Like ``RoundScanEngine``, the engine is stateless between calls:
    callers own the stacked ``EngineState`` and thread it through
    ``run_block``. The passed-in state is DONATED — use the returned
    state and drop the old reference.
    """

    def __init__(self, cfg, scenarios: Sequence[Scenario],
                 device_data: Optional[DeviceDataset] = None):
        if cfg.algo not in ENGINE_ALGOS:
            raise ValueError(f"unsupported algo {cfg.algo!r}")
        if not scenarios:
            raise ValueError("empty sweep")
        self.cfg = cfg
        self.scenarios = list(scenarios)
        S = len(self.scenarios)
        self.n_scenarios = S
        if device_data is not None:
            self.dd = device_data
        elif all(s.data is self.scenarios[0].data for s in self.scenarios):
            # seed/loss grids usually share one dataset draw — stage it
            # once and broadcast through the vmap (in_axes=None) instead
            # of stacking S identical (N, M, D) copies on device
            self.dd = stage_on_device(self.scenarios[0].data)
        else:
            self.dd = stage_scenarios_on_device(
                [s.data for s in self.scenarios])
        # counts is (N,) when the dataset is shared, (S, N) when stacked
        self.data_batched = self.dd.counts.ndim == 2
        self.n_clients = int(self.dd.counts.shape[-1])
        n_elig = [int(np.asarray(s.eligible).sum()) for s in self.scenarios]
        if min(n_elig) == 0:
            raise ValueError("a scenario has no eligible clients")
        cohorts = {min(cfg.clients_per_round, ne) for ne in n_elig}
        if len(cohorts) != 1:
            # the cohort is a static shape — scenarios whose eligible
            # sets clamp clients_per_round differently can't share a
            # program
            raise ValueError(f"scenarios disagree on cohort size: "
                             f"{sorted(cohorts)}")
        self.cohort = cohorts.pop()
        # per-scenario netsim knobs (static model flags must agree —
        # they pick the compiled program)
        nsims = self._nsims = [
            s.netsim if s.netsim is not None else cfg.netsim
            for s in self.scenarios]
        for i, ns in enumerate(nsims):
            if (ns.channel, ns.bw_ar1, ns.deadline, ns.down_channel,
                    ns.down_fallback) != \
                    (cfg.netsim.channel, cfg.netsim.bw_ar1,
                     cfg.netsim.deadline, cfg.netsim.down_channel,
                     cfg.netsim.down_fallback):
                raise ValueError(
                    f"scenario {i} selects different netsim models "
                    f"than the sweep config; only "
                    f"{SWEEP_VARYING_NETSIM_FIELDS} may vary per cell")
        if cfg.tra.per_client_loss:
            if any(s.packet_loss is None for s in self.scenarios):
                raise ValueError("tra.per_client_loss needs per-client "
                                 "rates on every Scenario (packet_loss)")
            loss_rate = jnp.asarray(np.stack(
                [np.asarray(s.packet_loss, np.float32)
                 for s in self.scenarios]))
        else:
            loss_rate = jnp.asarray(
                [s.loss_rate for s in self.scenarios], jnp.float32)
        if (cfg.netsim.bw_ar1 or cfg.netsim.deadline) \
                and any(s.upload_mbps is None for s in self.scenarios):
            raise ValueError("netsim bandwidth/deadline models need "
                             "per-client speeds on every Scenario "
                             "(upload_mbps)")
        # per-scenario selection knobs (static policy/traced flags must
        # agree — they pick the compiled program; with traced=True the
        # policy itself becomes the per-scenario one-hot)
        sels = self._sels = [s.sel if s.sel is not None else cfg.sel
                             for s in self.scenarios]
        for i, sc in enumerate(sels):
            ok = sc.traced == cfg.sel.traced and (
                cfg.sel.traced or sc.policy == cfg.sel.policy)
            if not ok:
                raise ValueError(
                    f"scenario {i} selects a different selection "
                    f"policy/traced mode than the sweep config; only "
                    f"{SWEEP_VARYING_SEL_FIELDS} may vary per cell "
                    f"(the policy itself only with sel.traced=True)")
        # per-scenario server-mode knobs (static mode/traced/buffer_k
        # must agree — they pick the compiled program; with traced=True
        # the mode itself becomes the per-scenario one-hot)
        srvs = self._srvs = [s.srv if s.srv is not None else cfg.srv
                             for s in self.scenarios]
        for i, sv in enumerate(srvs):
            ok = sv.traced == cfg.srv.traced \
                and sv.buffer_k == cfg.srv.buffer_k \
                and (cfg.srv.traced or sv.mode == cfg.srv.mode)
            if not ok:
                raise ValueError(
                    f"scenario {i} selects a different server mode / "
                    f"traced flag / buffer size than the sweep config; "
                    f"only {SWEEP_VARYING_SRV_FIELDS} may vary per "
                    f"cell (the mode itself only with srv.traced=True)")
        # per-scenario fault rates / defense knobs (faults.enabled and
        # defense.trim_k are static program structure and must agree)
        flts = self._flts = [
            s.faults if s.faults is not None else cfg.faults
            for s in self.scenarios]
        dfns = self._dfns = [
            s.defense if s.defense is not None else cfg.defense
            for s in self.scenarios]
        for i, (fl, df) in enumerate(zip(flts, dfns)):
            ok = fl.enabled == cfg.faults.enabled \
                and df.trim_k == cfg.defense.trim_k
            if not ok:
                raise ValueError(
                    f"scenario {i} selects a different faults.enabled "
                    f"/ defense.trim_k than the sweep config; only "
                    f"faults.{SWEEP_VARYING_FAULT_FIELDS} and defense."
                    f"{SWEEP_VARYING_DEF_FIELDS} may vary per cell")
        # per-scenario recovery knobs (traced flag and FEC group are
        # static program structure; with traced=True the policy itself
        # becomes the per-scenario one-hot)
        recs = self._recs = [
            s.recovery if s.recovery is not None else cfg.recovery
            for s in self.scenarios]
        for i, rc in enumerate(recs):
            ok = rc.traced == cfg.recovery.traced \
                and rc.group == cfg.recovery.group \
                and (cfg.recovery.traced
                     or rc.policy == cfg.recovery.policy)
            if not ok:
                raise ValueError(
                    f"scenario {i} selects a different recovery "
                    f"policy / traced flag / FEC group than the sweep "
                    f"config; only {SWEEP_VARYING_REC_FIELDS} may vary "
                    f"per cell (the policy itself only with "
                    f"recovery.traced=True)")
        # per-scenario loss-budget knobs (enabled is static structure)
        buds = self._buds = [
            s.lossbudget if s.lossbudget is not None else cfg.lossbudget
            for s in self.scenarios]
        for i, bc in enumerate(buds):
            if bc.enabled != cfg.lossbudget.enabled:
                raise ValueError(
                    f"scenario {i} toggles lossbudget.enabled against "
                    f"the sweep config; only {SWEEP_VARYING_BUD_FIELDS} "
                    f"may vary per cell")
        need_bw_score = cfg.sel.traced \
            or cfg.sel.policy == "bandwidth_threshold"
        if need_bw_score \
                and any(s.upload_mbps is None for s in self.scenarios):
            raise ValueError(
                "the bandwidth_threshold selection score (and the "
                "traced policy family) needs per-client speeds on "
                "every Scenario (upload_mbps)")
        if all(s.upload_mbps is not None for s in self.scenarios):
            sel_logbw = jnp.stack([log_upload_speeds(s.upload_mbps)
                                   for s in self.scenarios])
        else:
            sel_logbw = jnp.zeros((S, 0), jnp.float32)
        self.ctx = ScenarioCtx(
            base_key=jnp.stack([jax.random.PRNGKey(s.seed)
                                for s in self.scenarios]),
            loss_rate=loss_rate,
            eligible=jnp.asarray(np.stack(
                [np.asarray(s.eligible, bool) for s in self.scenarios])),
            sufficient=jnp.asarray(np.stack(
                [np.asarray(s.sufficient, np.float32)
                 for s in self.scenarios])),
            data=self.dd,
            burst_len=jnp.asarray([ns.burst_len for ns in nsims],
                                  jnp.float32),
            good_loss=jnp.asarray([ns.good_loss for ns in nsims],
                                  jnp.float32),
            bad_loss=jnp.asarray([ns.bad_loss for ns in nsims],
                                 jnp.float32),
            bw_rho=jnp.asarray([ns.bw_rho for ns in nsims], jnp.float32),
            deadline_s=jnp.asarray([ns.deadline_s for ns in nsims],
                                   jnp.float32),
            sel_threshold=jnp.asarray([sc.threshold_mbps for sc in sels],
                                      jnp.float32),
            sel_temp=jnp.asarray([sc.temperature for sc in sels],
                                 jnp.float32),
            sel_explore=jnp.asarray([sc.explore for sc in sels],
                                    jnp.float32),
            sel_policy=jnp.asarray(np.stack(
                [sel_mod.policy_onehot(sc.policy) for sc in sels])),
            sel_logbw=sel_logbw,
            srv_mode=jnp.asarray(np.stack(
                [async_mod.mode_onehot(sv.mode) for sv in srvs])),
            stale_alpha=jnp.asarray(
                [sv.staleness_alpha for sv in srvs], jnp.float32),
            grace_s=jnp.asarray([sv.grace_s for sv in srvs],
                                jnp.float32),
            f_corrupt=jnp.asarray([fl.corrupt_rate for fl in flts],
                                  jnp.float32),
            f_cscale=jnp.asarray([fl.corrupt_scale for fl in flts],
                                 jnp.float32),
            f_bitflip=jnp.asarray([fl.bitflip_rate for fl in flts],
                                  jnp.float32),
            f_fail=jnp.asarray([fl.fail_rate for fl in flts],
                               jnp.float32),
            f_flip=jnp.asarray([fl.flip_rate for fl in flts],
                               jnp.float32),
            f_echo=jnp.asarray([fl.echo_rate for fl in flts],
                               jnp.float32),
            d_screen=jnp.asarray([1.0 if df.screen else 0.0
                                  for df in dfns], jnp.float32),
            d_clip=jnp.asarray([faults_mod.clip_knob(df)
                                for df in dfns], jnp.float32),
            d_trim=jnp.asarray([1.0 if df.trim else 0.0
                                for df in dfns], jnp.float32),
            down_loss=jnp.asarray([ns.down_loss for ns in nsims],
                                  jnp.float32),
            down_deadline_s=jnp.asarray(
                [ns.down_deadline_s for ns in nsims], jnp.float32),
            rec_policy=jnp.asarray(np.stack(
                [rec_mod.recovery_onehot(rc.policy) for rc in recs])),
            rec_retries=jnp.asarray([rc.retries for rc in recs],
                                    jnp.float32),
            rec_backoff=jnp.asarray([rc.backoff for rc in recs],
                                    jnp.float32),
            bud_budget=jnp.asarray([bc.budget for bc in buds],
                                   jnp.float32),
            bud_ema=jnp.asarray([bc.ema for bc in buds], jnp.float32),
            bud_div=jnp.asarray([bc.div_gate for bc in buds],
                                jnp.float32))
        cache_key = (_static_key(cfg), self.cohort, self.data_batched)
        hit = cache_key in _SWEEP_CACHE
        fp = tele_mod.REGISTRY.record_lookup("sweep", cache_key, hit=hit)
        if not hit:
            step = make_round_step(cfg, self.cohort)
            ctx_axes = ScenarioCtx(base_key=0, loss_rate=0, eligible=0,
                                   sufficient=0,
                                   data=0 if self.data_batched else None,
                                   burst_len=0, good_loss=0, bad_loss=0,
                                   bw_rho=0, deadline_s=0,
                                   sel_threshold=0, sel_temp=0,
                                   sel_explore=0, sel_policy=0,
                                   sel_logbw=0, srv_mode=0,
                                   stale_alpha=0, grace_s=0,
                                   f_corrupt=0, f_cscale=0, f_bitflip=0,
                                   f_fail=0, f_flip=0, f_echo=0,
                                   d_screen=0, d_clip=0, d_trim=0,
                                   down_loss=0, down_deadline_s=0,
                                   rec_policy=0, rec_retries=0,
                                   rec_backoff=0, bud_budget=0,
                                   bud_ema=0, bud_div=0)
            vstep = jax.vmap(step, in_axes=(ctx_axes, 0, None))

            # named, so the compiled module is ``jit_sweep_block``
            def sweep_block(ctx, state, ts):
                return jax.lax.scan(lambda s, t: vstep(ctx, s, t), state,
                                    ts)

            _SWEEP_CACHE[cache_key] = (step, tele_mod.TimedProgram(
                jax.jit(sweep_block, donate_argnums=(1,)), "sweep", fp,
                "sweep"))
        self._step, self._block = _SWEEP_CACHE[cache_key]

    @classmethod
    def from_configs(cls, cfgs: Sequence[Any],
                     datas, nets=None) -> "SweepEngine":
        """Build a sweep from S per-scenario configs (seeds, loss rates
        and selection policies may differ; static structure must agree).

        ``datas`` is one shared ``FederatedDataset`` or a length-S
        sequence of per-scenario draws; ``nets`` likewise one shared
        ``ClientNetworks``, a length-S sequence, or None to sample from
        each scenario's seed (the ``FederatedServer`` default)."""
        cfgs = list(cfgs)
        S = len(cfgs)
        if S == 0:
            raise ValueError("empty config grid")
        sig0 = static_signature(cfgs[0])
        for i, c in enumerate(cfgs[1:], 1):
            if static_signature(c) != sig0:
                raise ValueError(
                    f"config {i} differs from config 0 in a static "
                    f"field; only {SWEEP_VARYING_FIELDS}, tra."
                    f"{SWEEP_VARYING_TRA_FIELDS}, netsim."
                    f"{SWEEP_VARYING_NETSIM_FIELDS}, sel."
                    f"{SWEEP_VARYING_SEL_FIELDS}, srv."
                    f"{SWEEP_VARYING_SRV_FIELDS}, faults."
                    f"{SWEEP_VARYING_FAULT_FIELDS}, defense."
                    f"{SWEEP_VARYING_DEF_FIELDS}, recovery."
                    f"{SWEEP_VARYING_REC_FIELDS} and lossbudget."
                    f"{SWEEP_VARYING_BUD_FIELDS} (plus sel.policy / "
                    f"srv.mode / recovery.policy under their "
                    f"traced=True) may vary in one sweep")
        if isinstance(datas, FederatedDataset):
            datas = [datas] * S
        if len(datas) != S:
            raise ValueError(f"expected {S} datasets, got {len(datas)}")
        if nets is None or not isinstance(nets, (list, tuple)):
            nets = [nets] * S
        if len(nets) != S:
            raise ValueError(f"expected {S} networks, got {len(nets)}")
        nets = [n if n is not None
                else sample_networks(np.random.default_rng(c.seed),
                                     d.n_clients)
                for c, d, n in zip(cfgs, datas, nets)]
        # batched eligibility staging: one (S, N) device mask covering
        # every scenario's selection policy
        eligible = np.asarray(stage_network_scenarios(
            nets, [c.selection for c in cfgs],
            eligible_ratios=[c.eligible_ratio for c in cfgs],
            thresholds_mbps=[c.tra.threshold_mbps for c in cfgs]))
        scen = [Scenario(seed=c.seed, loss_rate=c.tra.loss_rate,
                         sufficient=tra_mod.sufficiency_report(
                             n, c.tra.threshold_mbps),
                         eligible=eligible[i], data=d,
                         netsim=c.netsim, sel=c.sel, srv=c.srv,
                         faults=c.faults, defense=c.defense,
                         recovery=c.recovery, lossbudget=c.lossbudget,
                         packet_loss=n.packet_loss,
                         upload_mbps=n.upload_mbps)
                for i, (c, d, n) in enumerate(zip(cfgs, datas, nets))]
        return cls(cfgs[0], scen)

    # -- state --------------------------------------------------------------
    def init_states(self, param_init=None) -> EngineState:
        """Stacked per-scenario initial state; params are drawn from each
        scenario's seed exactly like ``FederatedServer``
        (``mlp_init(PRNGKey(seed))``). ``param_init`` overrides the
        per-scenario ``key -> params`` initializer (e.g. a differently
        sized MLP)."""
        init = mlp_init if param_init is None else param_init
        states = [init_engine_state(self.cfg,
                                    init(jax.random.PRNGKey(s.seed)),
                                    self.n_clients,
                                    base_key=jax.random.PRNGKey(s.seed),
                                    loss_rate=self.ctx.loss_rate[i],
                                    upload_mbps=s.upload_mbps,
                                    netsim=self._nsims[i])
                  for i, s in enumerate(self.scenarios)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    # -- execution ----------------------------------------------------------
    def run_block(self, states: EngineState, t0: int, k: int
                  ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Scan rounds [t0, t0+k) of ALL scenarios in one device
        program; flush logs to host demuxed scenario-major. Returns
        (states, {"loss": (S, k), "ids": (S, k, C)})."""
        ts = jnp.arange(t0, t0 + k, dtype=jnp.int32)
        states, logs = self._block(self.ctx, states, ts)
        # the scan stacks outputs time-major (k, S, ...); demux to
        # scenario-major on flush
        return states, {name: np.moveaxis(np.asarray(v), 0, 1)
                        for name, v in logs.items()}

    def run(self, n_rounds: Optional[int] = None
            ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Whole-grid convenience: init + scan every round in ONE
        dispatch. Returns (final stacked states, scenario-major logs)."""
        r = self.cfg.n_rounds if n_rounds is None else n_rounds
        return self.run_block(self.init_states(), 0, r)
