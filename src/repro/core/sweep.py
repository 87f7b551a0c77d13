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
hyper-parameters — must be shared across a sweep; ``SweepEngine``
checks each scenario's ``static_signature`` against the sweep config's
and raises on a mixed grid (split such a grid into one sweep per
static signature).

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
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry as tele_mod
from repro.core import tra as tra_mod
from repro.core.engine import (ENGINE_ALGOS, SWEEP_VARYING_BUD_FIELDS,
                               SWEEP_VARYING_DEF_FIELDS,
                               SWEEP_VARYING_FAULT_FIELDS,
                               SWEEP_VARYING_FIELDS,
                               SWEEP_VARYING_NETSIM_FIELDS,
                               SWEEP_VARYING_REC_FIELDS,
                               SWEEP_VARYING_SEL_FIELDS,
                               SWEEP_VARYING_SRV_FIELDS,
                               SWEEP_VARYING_TRA_FIELDS, EngineState,
                               RoundPlan, _static_key, fetch_logs,
                               init_engine_state, make_round_step,
                               scenario_ctx, static_signature)
from repro.data.synthetic import (DeviceDataset, FederatedDataset,
                                  stage_on_device,
                                  stage_scenarios_on_device)
from repro.network.trace import (eligible_mask_device, sample_networks,
                                 stage_network_scenarios)

# sweep-program cache, mirroring engine._STEP_CACHE: one compiled
# vmap(scan) program per (static config, cohort, shared-vs-stacked
# data); grids of any size S reuse it (jit re-specialises per shape).
_SWEEP_CACHE: Dict[Any, Any] = {}


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One cell of a paper grid (host-side description): its config,
    whose scenario knobs (``SWEEP_VARYING_*``) may differ from the
    sweep's while its ``static_signature`` may not, and its draws."""
    cfg: Any
    data: FederatedDataset        # this scenario's dataset draw
    sufficient: np.ndarray        # (N,) 0/1 sufficiency reports
    eligible: np.ndarray          # (N,) bool selection mask
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
    return Scenario(cfg=cfg, data=data, sufficient=sufficient,
                    eligible=eligible, packet_loss=nets.packet_loss,
                    upload_mbps=nets.upload_mbps)


def _first_difference(a, b) -> str:
    """The dotted name of the first field in which two configs differ
    (descending into nested configs), or "" when they agree."""
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if x == y:
            continue
        if dataclasses.is_dataclass(x) and type(x) is type(y):
            sub = _first_difference(x, y)
            return f"{f.name}.{sub}" if sub else f.name
        return f.name
    return ""


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
        if cfg.model.has_frozen:
            raise ValueError(
                f"client model {cfg.model.name!r} has a frozen tree: "
                f"sweeps stack every scenario's state, and a grid of "
                f"large models is not offered (run FederatedServer)")
        if not scenarios:
            raise ValueError("empty sweep")
        sig = static_signature(cfg)
        for i, s in enumerate(scenarios):
            if static_signature(s.cfg) != sig:
                raise ValueError(
                    f"scenario {i} differs from the sweep config in the "
                    f"static field "
                    f"{_first_difference(static_signature(s.cfg), sig)}; "
                    f"only {SWEEP_VARYING_FIELDS}, tra."
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
        self.plan = RoundPlan.of(cfg, self.cohort)
        for s in self.scenarios:
            self.plan.check_draws(s.packet_loss, s.upload_mbps)
        # each scenario's ctx, stacked; the data stays shared or
        # stacked as staged above. The speed draw rides every scenario
        # or none (the stacked score input is rectangular)
        speeds = all(s.upload_mbps is not None for s in self.scenarios)
        self.ctx = jax.tree.map(lambda *xs: jnp.stack(xs), *[
            scenario_ctx(s.cfg, s.eligible, s.sufficient, None,
                         s.packet_loss, s.upload_mbps if speeds else None)
            for s in self.scenarios])._replace(data=self.dd)
        cache_key = (_static_key(cfg), self.cohort, self.data_batched)
        hit = cache_key in _SWEEP_CACHE
        fp = tele_mod.REGISTRY.record_lookup("sweep", cache_key, hit=hit)
        if not hit:
            step = make_round_step(cfg, self.cohort)
            ctx_axes = jax.tree.map(lambda _: 0,
                                    self.ctx._replace(data=None))
            if self.data_batched:
                ctx_axes = ctx_axes._replace(data=0)
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
        scen = [Scenario(cfg=c, data=d,
                         sufficient=tra_mod.sufficiency_report(
                             n, c.tra.threshold_mbps),
                         eligible=eligible[i], packet_loss=n.packet_loss,
                         upload_mbps=n.upload_mbps)
                for i, (c, d, n) in enumerate(zip(cfgs, datas, nets))]
        return cls(cfgs[0], scen)

    # -- state --------------------------------------------------------------
    def init_states(self, param_init=None) -> EngineState:
        """Stacked per-scenario initial state; params are drawn from each
        scenario's seed exactly like ``FederatedServer``
        (the client model's trainable ``init(PRNGKey(seed))``).
        ``param_init`` overrides the per-scenario ``key -> params``
        initializer (e.g. a differently sized MLP)."""
        init = (lambda k: self.cfg.model.init(k)[0]) if param_init is None \
            else param_init
        states = [init_engine_state(self.plan,
                                    init(jax.random.PRNGKey(s.cfg.seed)),
                                    self.n_clients,
                                    base_key=jax.random.PRNGKey(s.cfg.seed),
                                    loss_rate=self.ctx.loss_rate[i],
                                    upload_mbps=s.upload_mbps,
                                    netsim=s.cfg.netsim)
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
        out = fetch_logs(logs)
        self._block.count(d2h=sum(v.nbytes for v in out.values()),
                          arrays=len(out))
        # the scan stacks outputs time-major (k, S, ...); demux to
        # scenario-major after the fetch
        return states, {name: np.moveaxis(v, 0, 1)
                        for name, v in out.items()}

    def run(self, n_rounds: Optional[int] = None
            ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Whole-grid convenience: init + scan every round in ONE
        dispatch. Returns (final stacked states, scenario-major logs)."""
        r = self.cfg.n_rounds if n_rounds is None else n_rounds
        return self.run_block(self.init_states(), 0, r)
