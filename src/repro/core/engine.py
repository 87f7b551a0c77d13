"""Device-resident round-scan engine.

Compiles a *block* of K federated rounds into a single
``jax.lax.scan`` program so sweeps are bounded by compute, not by
per-round Python dispatch and host<->device traffic. Everything a round
needs lives on device for the whole block:

  * client selection     — Gumbel top-k over the eligibility mask
                           (uniform without replacement over eligible),
  * PRNG                 — a pure ``fold_in(base_key, t)`` chain keyed on
                           the absolute round index, so any block
                           partitioning of the same run replays the same
                           randomness (replaces the host-side
                           ``hash((seed, t))`` key derivation),
  * training data        — pre-staged padded per-client batches
                           (`data/synthetic.stage_on_device`), sampled
                           in-scan with per-client ``randint`` bounds,
  * per-client state     — error-feedback memory, SCAFFOLD ``c_i`` and
                           AFL ``lambda`` are scan carries, gathered for
                           the cohort and scattered back each round,
  * TRA                  — the lossy-upload simulation runs in-scan and
                           the whole uplink step (EF re-inject, mask,
                           debias-aggregate, EF update, q-FedAvg norms)
                           is ONE pass over the packetised uploads via
                           ``kernels/uplink_fused`` (Pallas megakernel
                           on TPU, bit-identical jnp reference on
                           CPU/GPU),
  * network simulation   — the stateful netsim layer (`repro/netsim`)
                           rides the same scan: per-client
                           Gilbert–Elliott channel states and AR(1)
                           log-bandwidth levels are a ``NetSimState``
                           carry inside ``EngineState``, advanced
                           in-round (channel per packet via
                           ``kernels/netsim_mask``, bandwidth per
                           round) and consumed by the loss mask and
                           the deadline delivery model. The
                           ``channel="iid"`` default carries zero-size
                           arrays and is bit-identical to the
                           pre-netsim engine (tests/test_netsim.py),
  * logging              — per-round train loss and selected cohorts are
                           accumulated in scan outputs and flushed to
                           host once per block.

Scenario-varying inputs — PRNG base key, TRA loss rate, eligibility and
sufficiency masks, and the staged dataset — ride through the jits as a
traced ``ScenarioCtx`` argument rather than Python closure constants.
That is what lets `core/sweep.py` stack S scenarios behind a leading
axis and ``vmap`` the *same* step function over them: a whole paper
grid becomes one compiled program. Static structure (algorithm, debias
mode, cohort size, local steps, batch size, TRA on/off, error
feedback) stays in the closure and must be shared across a sweep.

``run_single`` jits the *same* step function for one round — that is the
per-round reference path `FederatedServer.run_round` uses, which is what
makes the scanned and sequential paths equivalent under a fixed seed
(see tests/test_engine.py).

``EngineState`` is donated on every engine jit (``donate_argnums``), so
the (N, D_up) error-feedback and SCAFFOLD buffers are updated in place
across dispatches instead of being copied every block
(tests/test_sweep.py asserts the buffer aliasing).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import async_agg as async_mod
from repro.core import client_updates as cu
from repro.core import lossbudget as bud_mod
from repro.core import selection as sel_mod
from repro.core import telemetry as tele_mod
from repro.core.async_agg import ArrivalBuffer
from repro.core.telemetry import TelemetryState
from repro.core.mlp import mlp_weighted_loss
from repro.core.tra import flatten_clients, unflatten_like
from repro.data.synthetic import DeviceDataset, stage_on_device
from repro.kernels.common import DENOM_EPS
from repro.kernels.fec_recover import ops as fec_ops
from repro.kernels.netsim_mask import ops as netsim_ops
from repro.kernels.robust_agg import ops as robust_ops
from repro.kernels.uplink_fused import ops as uplink_ops
from repro.netsim import faults as faults_mod
from repro.netsim import recovery as rec_mod
from repro.netsim.bandwidth import logbw_round_step
from repro.netsim.channel import ge_transition_probs
from repro.netsim.delivery import (MAX_LATENESS, arrival_lateness,
                                   deadline_delivered, grace_staleness,
                                   round_upload_seconds)
from repro.netsim.state import NetSimState, init_net_state
from repro.network.packets import n_packets
from repro.network.trace import log_upload_speeds

ENGINE_ALGOS = ("fedavg", "qfedavg", "pfedme", "perfedavg", "afl",
                "scaffold")


class EngineState(NamedTuple):
    """Scan carry. Unused fields (e.g. ``c_i`` for non-SCAFFOLD algos)
    are zero-size arrays that ride through the scan untouched."""
    params: Any           # model pytree
    ef_mem: jnp.ndarray   # (N, D_up) error-feedback memory, or (0,)
    c_global: jnp.ndarray  # (D,) SCAFFOLD server variate, or (0,)
    c_i: jnp.ndarray      # (N, D) SCAFFOLD client variates, or (0,)
    lam: jnp.ndarray      # (N,) AFL mixture weights (always allocated)
    net: NetSimState      # channel states + log-bandwidth levels
    #                       ((N,) each, or (0,) when netsim is off)
    # selection score memory (core/selection.py): last masked squared
    # update norm / last train loss per client, written for the cohort
    # each round and read by the gradient_norm / loss_aware policies at
    # the NEXT round's selection. (0,) when the policy needs neither.
    gnorm_mem: jnp.ndarray  # (N,) f32, or (0,)
    loss_mem: jnp.ndarray   # (N,) f32, or (0,)
    # last observed lateness (rounds past the deadline) per client,
    # scattered at the cohort each deadline round; read by the
    # staleness_aware selection policy. (0,) when not needed.
    stale_mem: jnp.ndarray  # (N,) f32, or (0,)
    # K-slot in-flight upload buffer (core/async_agg.py): late uploads
    # ride the scan sorted by arrival round and merge into the round
    # they land in, staleness-discounted. Zero-size when the server
    # mode carries no buffer (sync / semi_sync, untraced).
    buf: ArrivalBuffer
    # fault-model carries (repro/netsim/faults.py); (0,) when the fault
    # subsystem is compiled out (faults.enabled=False):
    # last GENUINE upload per client — what a stale-echo client replays
    echo_mem: jnp.ndarray   # (N, D_up) f32, or (0,)
    # cumulative quarantined-packet fraction per client — the
    # reputation the reputation_aware selection policy reads. (0,)
    # unless that policy (or traced selection) needs it.
    rep_mem: jnp.ndarray    # (N,) f32, or (0,)
    # device-resident telemetry accumulators (core/telemetry.py):
    # cumulative per-client participation / arrival / staleness /
    # quarantine aggregates at TelemetryConfig(level="full"); all (0,)
    # otherwise — the default "off" compiles the subsystem out and is
    # locked bitwise vs the frozen PR-8 step (tests/_legacy_engine_v8).
    tele: TelemetryState
    # downlink stale-model buffer: each client's last-RECEIVED model
    # coordinates — the stale-parameter fallback source when downlink
    # packets drop (netsim down_channel + down_fallback="stale").
    # (0,) when the downlink model is off or fallback is zero-fill.
    stale_model: jnp.ndarray = jnp.zeros((0,), jnp.float32)  # (N, D)
    # adaptive loss-budget controller carries (core/lossbudget.py):
    # per-client recovery escalation level (0=one_shot, 1=fec, 2=arq)
    # and realized-loss EMA. (0,) unless lossbudget.enabled.
    bud_level: jnp.ndarray = jnp.zeros((0,), jnp.float32)    # (N,)
    bud_loss: jnp.ndarray = jnp.zeros((0,), jnp.float32)     # (N,)


class ScenarioCtx(NamedTuple):
    """Everything a round may vary *per scenario* without recompiling.

    These are traced jit arguments (never closure constants); under the
    sweep engine every field gains a leading scenario axis and the step
    is vmapped over it. Anything NOT in here — algorithm, debias mode,
    cohort size, local steps, batch size, TRA enabled, error feedback,
    the netsim channel/bandwidth/deadline model *selection* — is baked
    into the step closure and must be identical across a sweep.
    """
    base_key: jnp.ndarray    # (2,) uint32 PRNG root of the fold_in chain
    loss_rate: jnp.ndarray   # () f32 nominal drop rate, or (N,) f32
    #                          per-client rates (tra.per_client_loss —
    #                          the trace model's exponential fit)
    eligible: jnp.ndarray    # (N,) bool selection mask
    sufficient: jnp.ndarray  # (N,) f32 1-bit sufficiency reports
    data: DeviceDataset      # staged train set (train_x/train_y/counts)
    # netsim scenario knobs (unused-but-traced when the corresponding
    # model is off; XLA prunes them from the program)
    burst_len: jnp.ndarray   # () f32 E[bad sojourn] in packets (GE)
    good_loss: jnp.ndarray   # () f32 GOOD-state per-packet loss (GE)
    bad_loss: jnp.ndarray    # () f32 BAD-state per-packet loss (GE)
    bw_rho: jnp.ndarray      # () f32 AR(1) round-to-round correlation
    deadline_s: jnp.ndarray  # () f32 per-round upload deadline
    # selection-policy knobs (core/selection.py; policy id is static,
    # or traced as the one-hot below when cfg.sel.traced)
    sel_threshold: jnp.ndarray  # () f32 bandwidth_threshold cut (Mbps)
    sel_temp: jnp.ndarray    # () f32 softmax temperature on the score
    sel_explore: jnp.ndarray  # () f32 0 = pure policy, 1 = uniform
    sel_policy: jnp.ndarray  # (len(POLICIES),) f32 one-hot (traced
    #                          policy mode; unused-but-traced otherwise)
    sel_logbw: jnp.ndarray   # (N,) f32 static log upload speeds for
    #                          the bandwidth score, or (0,) when the
    #                          trace draw wasn't provided
    # server aggregation mode knobs (core/async_agg.py; the mode is
    # static, or traced as the one-hot below when cfg.srv.traced)
    srv_mode: jnp.ndarray    # (len(async_agg.MODES),) f32 one-hot
    stale_alpha: jnp.ndarray  # () f32 staleness discount exponent
    grace_s: jnp.ndarray     # () f32 semi_sync grace window (seconds)
    # fault-injection rates + defense gates (repro/netsim/faults.py;
    # unused-but-traced when faults.enabled=False — XLA prunes them)
    f_corrupt: jnp.ndarray   # () f32 P(packet Gaussian-corrupted)
    f_cscale: jnp.ndarray    # () f32 corruption noise stddev
    f_bitflip: jnp.ndarray   # () f32 P(packet single-bit flip)
    f_fail: jnp.ndarray      # () f32 P(client NaN device failure)
    f_flip: jnp.ndarray      # () f32 P(client sign-flip byzantine)
    f_echo: jnp.ndarray      # () f32 P(client stale-echo replay)
    d_screen: jnp.ndarray    # () f32 gate: finite-screen quarantine
    d_clip: jnp.ndarray      # () f32 clip norm (faults.CLIP_OFF = off)
    d_trim: jnp.ndarray      # () f32 gate: trimmed-mean aggregation
    # downlink broadcast-loss knobs (netsim down_channel is static;
    # unused-but-traced when the downlink model is off)
    down_loss: jnp.ndarray   # () f32 nominal downlink drop rate
    down_deadline_s: jnp.ndarray  # () f32 broadcast deadline (<=0 off)
    # recovery-policy knobs (netsim/recovery.py; the policy is static,
    # or traced as the one-hot below when cfg.recovery.traced)
    rec_policy: jnp.ndarray  # (len(RECOVERY_POLICIES),) f32 one-hot
    rec_retries: jnp.ndarray  # () f32 ARQ retry budget m
    rec_backoff: jnp.ndarray  # () f32 ARQ per-resend time cost
    # adaptive loss-budget controller knobs (core/lossbudget.py;
    # ``enabled`` is static, these ride the trace)
    bud_budget: jnp.ndarray  # () f32 realized-loss EMA ceiling
    bud_ema: jnp.ndarray     # () f32 EMA coefficient beta
    bud_div: jnp.ndarray     # () f32 update-norm divergence gate


def gumbel_topk_select(key, eligible: jnp.ndarray, k: int) -> jnp.ndarray:
    """Uniform sample of ``k`` clients without replacement from the
    eligible set, entirely on device (Gumbel top-k with uniform
    weights). Back-compat alias: the score-weighted generalisation —
    the engine's selection-policy family — lives in
    ``core/selection.py`` (``select_clients``)."""
    return sel_mod.select_clients(key, None, eligible, k)


def fused_debias_aggregate(xp: jnp.ndarray, pkt_mask: jnp.ndarray,
                           weights: jnp.ndarray, *, mode: str, d_up: int,
                           kept=None, sufficient=None, loss_rate=None,
                           mult=None) -> jnp.ndarray:
    """Debiased weighted aggregate of the (implicitly) masked uploads.

    xp: (C, P, F) packetised UNMASKED uploads; pkt_mask: (C, P);
    weights: (C,). Reference-path delegate into the uplink megakernel
    ops (`kernels/uplink_fused`): the packet mask, per-mode debias
    scaling and client weights fold into a single einsum, so the masked
    per-client tensor is never materialised. Numerically equivalent to
    ``kernels/tra_agg/ops.tra_aggregate_packed`` on pre-masked inputs
    for every mode in DEBIAS_MODES — locked by
    tests/test_sweep.py::test_fused_agg_matches_kernel_ops.

    kept (C,) is the coordinate-weighted kept fraction (required for
    ``per_client_rate``); sufficient (C,) and loss_rate () feed
    ``group_rate``; ``mult`` scales clients on top of ``weights``
    without entering the denominator (q-FedAvg's F^q factors).
    """
    agg, _, _ = uplink_ops.uplink_round(
        xp, pkt_mask, weights, mode=mode, d_up=d_up, kept=kept,
        sufficient=sufficient, loss_rate=loss_rate, mult=mult, impl="ref")
    return agg


# FLConfig fields a scenario may vary without changing program structure;
# everything else must agree across engines sharing a compiled step.
SWEEP_VARYING_FIELDS = ("seed", "selection", "eligible_ratio")
SWEEP_VARYING_TRA_FIELDS = ("loss_rate", "threshold_mbps")
SWEEP_VARYING_NETSIM_FIELDS = ("burst_len", "good_loss", "bad_loss",
                               "bw_rho", "deadline_s", "down_loss",
                               "down_deadline_s")
# selection-policy knobs (core/selection.py); the policy NAME joins
# them when cfg.sel.traced (it rides ScenarioCtx as a one-hot then)
SWEEP_VARYING_SEL_FIELDS = sel_mod.SWEEP_VARYING_SEL_FIELDS
# server-mode knobs (core/async_agg.py); the mode NAME joins them when
# cfg.srv.traced (it rides ScenarioCtx as a one-hot then)
SWEEP_VARYING_SRV_FIELDS = async_mod.SWEEP_VARYING_SRV_FIELDS
# fault rates and defense gates (repro/netsim/faults.py); only
# faults.enabled and defense.trim_k are static program structure
SWEEP_VARYING_FAULT_FIELDS = faults_mod.SWEEP_VARYING_FAULT_FIELDS
SWEEP_VARYING_DEF_FIELDS = faults_mod.SWEEP_VARYING_DEF_FIELDS
# recovery-policy knobs (netsim/recovery.py); the policy NAME joins
# them when cfg.recovery.traced (it rides ScenarioCtx as a one-hot)
SWEEP_VARYING_REC_FIELDS = rec_mod.SWEEP_VARYING_REC_FIELDS
# loss-budget controller knobs (core/lossbudget.py); only ``enabled``
# is static program structure
SWEEP_VARYING_BUD_FIELDS = bud_mod.SWEEP_VARYING_BUD_FIELDS


def static_signature(cfg):
    """The config with scenario-varying knobs normalised away. Two
    configs produce the same compiled round step (and may share a
    sweep) iff their signatures are equal."""
    tra = dataclasses.replace(
        cfg.tra, **{f: 0.0 for f in SWEEP_VARYING_TRA_FIELDS})
    ns = dataclasses.replace(
        cfg.netsim, **{f: 0.0 for f in SWEEP_VARYING_NETSIM_FIELDS})
    sel = dataclasses.replace(
        cfg.sel, **{f: 0.0 for f in SWEEP_VARYING_SEL_FIELDS})
    if sel.traced:
        # the policy choice itself is traced (ScenarioCtx.sel_policy):
        # traced configs share one program across all policies
        sel = dataclasses.replace(sel, policy="uniform")
    srv = dataclasses.replace(
        cfg.srv, **{f: 0.0 for f in SWEEP_VARYING_SRV_FIELDS})
    if srv.traced:
        # the server mode itself is traced (ScenarioCtx.srv_mode):
        # traced configs share one program across all three modes
        srv = dataclasses.replace(srv, mode="sync")
    flt = dataclasses.replace(
        cfg.faults, **{f: 0.0 for f in SWEEP_VARYING_FAULT_FIELDS})
    dfn = dataclasses.replace(cfg.defense, **faults_mod.DEF_NEUTRAL)
    rec = dataclasses.replace(
        cfg.recovery, **{f: 0.0 for f in SWEEP_VARYING_REC_FIELDS})
    if rec.traced:
        # the recovery policy itself is traced (ScenarioCtx.rec_policy):
        # traced configs share one program across all three policies
        rec = dataclasses.replace(rec, policy="one_shot")
    bud = dataclasses.replace(
        cfg.lossbudget, **{f: 0.0 for f in SWEEP_VARYING_BUD_FIELDS})
    return dataclasses.replace(
        cfg, tra=tra, netsim=ns, sel=sel, srv=srv, faults=flt,
        defense=dfn, recovery=rec, lossbudget=bud, seed=0,
        selection="all", eligible_ratio=1.0)


def _static_key(cfg):
    """Hashable cache key for the compiled-program caches (primitives
    only — ``astuple`` recurses into the nested TRAConfig). Beyond the
    sweep-varying fields, the round/eval schedule and engine-mode knobs
    are normalised away too: they drive the block loop, never the
    compiled step, so configs differing only there share programs. The
    resolved uplink implementation (megakernel vs jnp reference — env /
    backend dependent) changes the traced program, so it is part of the
    key: flipping ``REPRO_UPLINK_IMPL`` retraces instead of replaying a
    stale cache entry."""
    return (dataclasses.astuple(dataclasses.replace(
        static_signature(cfg), n_rounds=0, eval_every=0, engine="scan")),
        uplink_ops.resolved_impl(), netsim_ops.resolved_impl(),
        robust_ops.resolved_impl(), fec_ops.resolved_impl())


# step/jit cache shared across engine instances: scenario-varying values
# are traced ScenarioCtx arguments, so every engine (and server) with the
# same static config reuses ONE compiled program per input shape instead
# of recompiling per instance — grid drivers construct engines per cell
# for free after the first.
_STEP_CACHE: Dict[Any, Any] = {}


def _cached_jits(cfg, cohort: int):
    # validate BEFORE the cache lookup: the key normalises sweep-
    # varying fields away, so an invalid config (e.g. defenses with
    # faults.enabled=False) can collide with a valid cached program
    # and would otherwise skip its construction-time checks
    validate_round_config(cfg)
    key = (_static_key(cfg), cohort)
    hit = key in _STEP_CACHE
    # every lookup logs the static-signature fingerprint (hit or
    # insert) to the program registry — two configs silently colliding
    # onto one program is diagnosable (and raises) there, and the
    # timing wrapper books compile/exec time against the same key.
    fp = tele_mod.REGISTRY.record_lookup("engine", key, hit=hit)
    if not hit:
        step = make_round_step(cfg, cohort)

        # a named function, so the compiled module (and its runs in a
        # profiler trace) is ``jit_round_block``
        def round_block(ctx, state, ts):
            return jax.lax.scan(lambda s, t: step(ctx, s, t), state, ts)

        single = tele_mod.TimedProgram(
            jax.jit(step, donate_argnums=(1,)), "engine", fp, "round")
        block = tele_mod.TimedProgram(
            jax.jit(round_block, donate_argnums=(1,)), "engine", fp,
            "block")
        _STEP_CACHE[key] = (step, single, block)
    return _STEP_CACHE[key]


def init_engine_state(cfg, params, n_clients: int, *, base_key=None,
                      loss_rate=None, upload_mbps=None,
                      netsim=None) -> EngineState:
    """Fresh engine state for one scenario (used by both the single
    engine and, stacked, by the sweep engine). ``params`` are copied:
    the engine jits DONATE the state, and the caller's arrays must not
    be destroyed with it.

    The netsim carry (Gilbert–Elliott channel states, log-bandwidth
    levels) initialises from the scenario's PRNG root / loss rate /
    static speed draw; the defaults reconstruct the single-engine
    values from ``cfg`` so existing callers stay source-compatible.
    """
    N = n_clients
    params = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
    vec0 = ravel_pytree(params)[0]
    D = vec0.shape[0]
    # SCAFFOLD uploads (dw ++ dc) ride one TRA stream, so its EF
    # memory covers the concatenated 2D vector.
    up_dim = 2 * D if cfg.algo == "scaffold" else D
    if base_key is None:
        base_key = jax.random.PRNGKey(cfg.seed)
    if loss_rate is None:
        loss_rate = jnp.float32(cfg.tra.loss_rate)
    return EngineState(
        params=params,
        ef_mem=jnp.zeros((N, up_dim), jnp.float32)
        if cfg.error_feedback else jnp.zeros((0,), jnp.float32),
        c_global=jnp.zeros((D,), jnp.float32)
        if cfg.algo == "scaffold" else jnp.zeros((0,), jnp.float32),
        c_i=jnp.zeros((N, D), jnp.float32)
        if cfg.algo == "scaffold" else jnp.zeros((0,), jnp.float32),
        lam=jnp.ones((N,), jnp.float32) / N,
        net=init_net_state(cfg.netsim if netsim is None else netsim, N,
                           base_key=base_key, loss_rate=loss_rate,
                           upload_mbps=upload_mbps),
        gnorm_mem=jnp.zeros((N,), jnp.float32)
        if cfg.sel.traced or cfg.sel.policy == "gradient_norm"
        else jnp.zeros((0,), jnp.float32),
        loss_mem=jnp.zeros((N,), jnp.float32)
        if cfg.sel.traced or cfg.sel.policy == "loss_aware"
        else jnp.zeros((0,), jnp.float32),
        stale_mem=jnp.zeros((N,), jnp.float32)
        if cfg.sel.traced or cfg.sel.policy == "staleness_aware"
        else jnp.zeros((0,), jnp.float32),
        buf=async_mod.init_arrival_buffer(cfg.srv.buffer_k, up_dim)
        if cfg.srv.traced or cfg.srv.mode == "async"
        else async_mod.empty_arrival_buffer(),
        echo_mem=jnp.zeros((N, up_dim), jnp.float32)
        if cfg.faults.enabled else jnp.zeros((0,), jnp.float32),
        rep_mem=jnp.zeros((N,), jnp.float32)
        if cfg.faults.enabled
        and (cfg.sel.traced or cfg.sel.policy == "reputation_aware")
        else jnp.zeros((0,), jnp.float32),
        tele=tele_mod.init_telemetry_state(cfg.telemetry, N),
        # every client starts having "received" the initial broadcast
        # (training begins from a known, fully-delivered init)
        stale_model=jnp.tile(vec0.astype(jnp.float32)[None, :], (N, 1))
        if (cfg.netsim.down_channel != "off"
            and cfg.netsim.down_fallback == "stale")
        else jnp.zeros((0,), jnp.float32),
        bud_level=jnp.zeros((N,), jnp.float32)
        if cfg.lossbudget.enabled else jnp.zeros((0,), jnp.float32),
        bud_loss=jnp.zeros((N,), jnp.float32)
        if cfg.lossbudget.enabled else jnp.zeros((0,), jnp.float32),
    )


def validate_round_config(cfg) -> None:
    """Cross-subsystem static-config checks, raised at engine
    construction (NOT inside the program cache: the cache key
    normalises sweep-varying fields away, so these must run before
    any cache lookup)."""
    tra_cfg = cfg.tra
    ns = cfg.netsim
    debias = tra_cfg.debias
    if ns.channel != "iid" and not tra_cfg.enabled:
        raise ValueError(
            f"netsim channel={ns.channel!r} models lossy TRA uploads "
            f"and requires tra.enabled=True (with TRA off, uploads are "
            f"reliable and the channel would be silently inert)")
    sel = cfg.sel
    traced_sel = sel.traced
    policy = sel.policy
    if not traced_sel and policy == "netsim_state" \
            and ns.channel != "gilbert_elliott":
        raise ValueError(
            "selection policy 'netsim_state' scores the Gilbert-"
            "Elliott channel state and requires "
            "netsim.channel='gilbert_elliott' (with the iid channel "
            "there is no state to prefer)")
    if not traced_sel and policy == "staleness_aware" \
            and not ns.deadline:
        raise ValueError(
            "selection policy 'staleness_aware' scores observed "
            "deadline lateness and requires netsim.deadline=True "
            "(without a deadline nothing is ever late)")
    srv_cfg = cfg.srv
    nonsync = srv_cfg.traced or srv_cfg.mode != "sync"
    use_buf = srv_cfg.traced or srv_cfg.mode == "async"
    if nonsync and not ns.deadline:
        raise ValueError(
            "server modes semi_sync/async (and srv.traced, which "
            "includes them) schedule uploads by arrival time and "
            "require netsim.deadline=True")
    if use_buf and debias == "per_coord_count":
        raise ValueError(
            "the async arrival buffer composes with scalar-"
            "denominator debias modes only; per_coord_count keeps "
            "per-coordinate denominators that cannot be re-weighted "
            "after the fact (use semi_sync, or another debias mode)")
    dfn_cfg = cfg.defense
    use_faults = cfg.faults.enabled
    trim_k = dfn_cfg.trim_k
    if not use_faults and (dfn_cfg.screen or dfn_cfg.clip
                           or dfn_cfg.trim or trim_k > 0):
        raise ValueError(
            "defenses (screen/clip/trim/trim_k) require "
            "faults.enabled=True — the robust uplink path is only "
            "compiled with the fault model (enable it with zero rates "
            "for a fault-free defended run)")
    if dfn_cfg.trim and trim_k < 1:
        raise ValueError(
            "defense.trim=True needs trim_k >= 1 (the static per-side "
            "trim count that sizes the extraction loop)")
    if trim_k > 0 and debias == "per_coord_count":
        raise ValueError(
            "trimmed-mean aggregation replaces the weighted mean and "
            "cannot compose with per_coord_count's per-coordinate "
            "denominators (use another debias mode, or trim_k=0)")
    if not traced_sel and policy == "reputation_aware" \
            and not use_faults:
        raise ValueError(
            "selection policy 'reputation_aware' scores quarantine "
            "counts and requires faults.enabled=True (without the "
            "fault path nothing is ever quarantined)")
    rec_cfg = cfg.recovery
    use_rec = rec_cfg.traced or rec_cfg.policy != "one_shot"
    if use_rec and not tra_cfg.enabled:
        raise ValueError(
            "recovery policies act on the lossy TRA uplink mask and "
            "require tra.enabled=True (with TRA off, uploads are "
            "reliable and there is nothing to recover)")
    if cfg.lossbudget.enabled and not rec_cfg.traced:
        raise ValueError(
            "the loss-budget controller mixes recovery policies "
            "per client and requires recovery.traced=True (all three "
            "policies must be compiled into the step)")
    if not traced_sel and policy == "recovery_pressure" \
            and not cfg.lossbudget.enabled:
        raise ValueError(
            "selection policy 'recovery_pressure' scores the loss-"
            "budget controller's escalation state and requires "
            "lossbudget.enabled=True (without the controller there is "
            "no pressure signal)")


# The round's stages, in the order the step runs them. Each names a
# ``jax.named_scope``, so every op of the compiled program carries its
# stage in its ``op_name`` metadata, and a profiler trace's device ops
# can be summed by stage (bench/program_trace.py).
STAGES = ("draws", "select", "gather", "downlink", "train", "faults",
          "uplink", "server", "telemetry")


class _StageScope:
    """``stage("gather")`` closes the open stage's ``jax.named_scope``
    and opens ``gather``'s, so each op the step traces falls in one
    stage. Names only: the traced math is unchanged."""

    def __init__(self):
        self._open = None

    def __call__(self, name: str) -> None:
        assert name in STAGES, name
        self.close()
        self._open = jax.named_scope(name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def make_round_step(cfg, cohort: int):
    """Build the round step ``step(ctx, state, t) -> (state, logs)``.

    ``ctx`` carries every scenario-varying input as traced values; the
    returned step is what ``RoundScanEngine`` jits for one scenario and
    what ``SweepEngine`` vmaps over a stacked ctx/state for S scenarios
    in one program. N (client count), M (padded set length) and the
    model dimension come from the traced shapes, so the same step works
    for any same-shaped scenario.
    """
    tra_cfg = cfg.tra
    hyper = cfg.hyper()
    algo = cfg.algo
    ef = cfg.error_feedback
    C = cohort
    steps, bs = cfg.local_steps, cfg.batch_size
    F = tra_cfg.packet_floats
    debias = tra_cfg.debias
    local = None if algo == "scaffold" else cu.LOCAL_FNS[algo]
    # netsim model selection is static (program structure); its knobs
    # (burst length, loss emissions, rho, deadline) are traced ctx
    # fields and may vary per scenario.
    ns = cfg.netsim
    validate_round_config(cfg)
    use_ge = ns.channel == "gilbert_elliott"
    use_bw = ns.bw_ar1
    use_dl = ns.deadline
    # selection policy: the id (or "traced") is static program
    # structure; its knobs ride ScenarioCtx (core/selection.py)
    sel = cfg.sel
    traced_sel = sel.traced
    policy = sel.policy
    need_gnorm = traced_sel or policy == "gradient_norm"
    need_loss = traced_sel or policy == "loss_aware"
    need_stale = traced_sel or policy == "staleness_aware"
    # server aggregation mode (core/async_agg.py): the mode (or
    # "traced") and the buffer size are static program structure; the
    # staleness exponent and grace window ride ScenarioCtx.
    srv_cfg = cfg.srv
    traced_srv = srv_cfg.traced
    srv_mode = srv_cfg.mode
    use_buf = traced_srv or srv_mode == "async"
    nonsync = traced_srv or srv_mode != "sync"
    # fault model + defenses (repro/netsim/faults.py):
    # ``faults.enabled`` is the single static switch for the whole
    # subsystem; every rate and every defense gate is traced.
    # ``defense.trim_k`` alone is static (extraction-loop extent).
    flt_cfg = cfg.faults
    dfn_cfg = cfg.defense
    use_faults = flt_cfg.enabled
    trim_k = dfn_cfg.trim_k
    need_rep = use_faults and (traced_sel
                               or policy == "reputation_aware")
    # telemetry level is static program structure (core/telemetry.py):
    # "off" compiles the subsystem out entirely — the step below is
    # then bitwise the frozen PR-8 step (tests/_legacy_engine_v8.py).
    tele_cfg = cfg.telemetry
    tele_on = tele_cfg.level != "off"
    # recovery-policy family (netsim/recovery.py): the policy (or
    # "traced") and the FEC group size are static program structure;
    # retries/backoff ride ScenarioCtx. ``use_rec`` compiles all three
    # recovery paths in — the one_shot default compiles them OUT and
    # is bitwise the frozen PR-9 step (tests/_legacy_engine_v9.py).
    rec_cfg = cfg.recovery
    use_rec = rec_cfg.traced or rec_cfg.policy != "one_shot"
    rec_group = rec_cfg.group
    n_pol = len(rec_mod.RECOVERY_POLICIES)
    # adaptive loss-budget controller (core/lossbudget.py): enabled is
    # the single static switch; budget/ema/div_gate are traced.
    use_bud = cfg.lossbudget.enabled
    # downlink broadcast loss (netsim): the channel choice and the
    # fallback are static; down_loss / down_deadline_s are traced. The
    # "off" default broadcasts losslessly — shared params, bitwise the
    # frozen PR-9 step.
    use_down = ns.down_channel != "off"
    down_ge = ns.down_channel == "gilbert_elliott"
    down_stale = ns.down_fallback == "stale"

    def step(ctx: ScenarioCtx, state: EngineState, t):
        stage = _StageScope()
        try:
            return staged_step(ctx, state, t, stage)
        finally:
            stage.close()

    def staged_step(ctx, state, t, stage):
        dd = ctx.data
        N = dd.counts.shape[0]
        afl_len = min(64, dd.train_x.shape[1])
        params = state.params
        stage("draws")
        old_vec, _ = ravel_pytree(params)
        # one threefry invocation covers the whole round: selection
        # gumbels, batch indices and the TRA packet draws (upload
        # width is static at trace time, so P is known here). The GE
        # channel needs a second (C, P) block — emission draws on top
        # of the transition draws — appended so the iid slices (and
        # hence the iid programs) are untouched.
        D_model = old_vec.shape[0]
        D_up = 2 * D_model if algo == "scaffold" else D_model
        P = n_packets(D_up, F)
        n_batch = C * steps * bs
        n_tra = 2 * C * P if use_ge else C * P
        # recovery / downlink blocks are APPENDED after the legacy
        # slices. NOTE: threefry uniforms are NOT prefix-stable in the
        # total draw count, so what keeps the default programs bitwise
        # is that their TOTAL is unchanged (n_rec = n_down = 0) — and
        # what makes a traced recovery grid cell bitwise equal to its
        # static single run is that both programs draw the SAME total
        # (use_rec always draws both the ARQ and the parity blocks).
        gn = rec_mod.fec_groups(P, rec_group) if use_rec else 0
        n_rec = C * P + C * gn if use_rec else 0
        P_dn = n_packets(D_model, F)
        n_down = (2 * C * P_dn if down_ge else C * P_dn) \
            if use_down else 0
        key = jax.random.fold_in(ctx.base_key, t)
        u_all = jax.random.uniform(
            key, (N + n_batch + n_tra + n_rec + n_down,),
            minval=1e-12, maxval=1.0)
        u_sel = u_all[:N]
        u_idx = u_all[N:N + n_batch].reshape(C, steps, bs)
        u_tra = u_all[N + n_batch:N + n_batch + C * P].reshape(C, P)
        u_emit = u_all[N + n_batch + C * P:
                       N + n_batch + n_tra].reshape(C, P) \
            if use_ge else None
        off = N + n_batch + n_tra
        u_arq = u_par = None
        if use_rec:
            u_arq = u_all[off:off + C * P].reshape(C, P)
            u_par = u_all[off + C * P:off + n_rec].reshape(C, gn)
            off += n_rec
        u_dt = u_de = None
        if use_down:
            u_dt = u_all[off:off + C * P_dn].reshape(C, P_dn)
            if down_ge:
                u_de = u_all[off + C * P_dn:
                             off + 2 * C * P_dn].reshape(C, P_dn)

        stage("select")
        # selection: weighted Gumbel-top-k over the eligibility mask.
        # Scores read the CARRY (previous round's channel/bandwidth/
        # score memory) — selection happens before this round's
        # training, exactly like a real server. policy="uniform"
        # (logits None) evaluates the legacy expression bitwise.
        sel_bw = state.net.logbw if use_bw else ctx.sel_logbw
        if traced_sel:
            logits = sel_mod.traced_policy_logits(
                ctx.sel_policy, temperature=ctx.sel_temp,
                explore=ctx.sel_explore,
                threshold_mbps=ctx.sel_threshold, logbw=sel_bw,
                gnorm_mem=state.gnorm_mem, loss_mem=state.loss_mem,
                channel=state.net.channel, stale_mem=state.stale_mem,
                rep_mem=state.rep_mem, bud_level=state.bud_level,
                bud_loss=state.bud_loss, n_clients=N)
        else:
            logits = sel_mod.policy_logits(
                policy, temperature=ctx.sel_temp,
                explore=ctx.sel_explore,
                threshold_mbps=ctx.sel_threshold, logbw=sel_bw,
                gnorm_mem=state.gnorm_mem, loss_mem=state.loss_mem,
                channel=state.net.channel, stale_mem=state.stale_mem,
                rep_mem=state.rep_mem, bud_level=state.bud_level,
                bud_loss=state.bud_loss)
        ids = sel_mod.select_from_uniforms(u_sel, logits, ctx.eligible,
                                           C)
        stage("gather")
        counts = dd.counts[ids]                              # (C,)
        idx = jnp.minimum((u_idx * counts[:, None, None]
                           ).astype(jnp.int32), counts[:, None, None] - 1)
        # direct (client, sample) gather — never materialises the
        # cohort's full padded datasets inside the scan
        cid = ids[:, None, None]
        X = dd.train_x[cid, idx]                 # (C, steps, bs, d)
        Y = dd.train_y[cid, idx]                 # (C, steps, bs)
        w = counts.astype(jnp.float32)
        weights = w / w.sum()
        suff = ctx.sufficient[ids]

        stage("downlink")
        # downlink broadcast: packetise the MODEL (D_model — scaffold's
        # control variate broadcast stays lossless, the documented
        # simplification), drop packets through the per-client downlink
        # channel, and fall back per coordinate — "stale" keeps the
        # client's last-received values (the stale_model carry),
        # "zero" is the naive baseline. Clients then train from their
        # own EFFECTIVE parameters instead of the shared broadcast.
        net_down = state.net.down
        eff_vec = None      # (C, D_model) per-client effective params
        dn_frac = None      # realized downlink loss (telemetry)
        if use_down:
            if down_ge:
                dp_gb, dp_bg = ge_transition_probs(
                    ctx.down_loss, ctx.burst_len, ctx.good_loss,
                    ctx.bad_loss)
                dmask, ds_fin = netsim_ops.ge_packet_mask(
                    u_dt, u_de, net_down[ids], dp_gb, dp_bg,
                    ctx.good_loss, ctx.bad_loss)
                net_down = net_down.at[ids].set(ds_fin)
            else:
                dmask = (u_dt >= ctx.down_loss).astype(jnp.float32)
            if use_bw or use_dl:
                # broadcast deadline: the whole model misses when
                # pushing P_dn packets at the client's current
                # (carried) bandwidth overruns the traced gate; <= 0
                # disables. Without a bandwidth carry the knob is
                # inert (see NetSimConfig).
                dsecs = round_upload_seconds(
                    P_dn, F, jnp.exp(state.net.logbw[ids]),
                    ctx.down_loss, jnp.zeros((C,), bool))
                dok = jnp.where(
                    ctx.down_deadline_s > 0.0,
                    deadline_delivered(dsecs, ctx.down_deadline_s),
                    1.0)
                dmask = dmask * dok[:, None]
            coord_dn = jnp.repeat(dmask, F, axis=1)[:, :D_model]
            stale_rows = state.stale_model[ids] if down_stale \
                else jnp.zeros((C, D_model), jnp.float32)
            eff_vec = coord_dn * old_vec[None, :] \
                + (1.0 - coord_dn) * stale_rows
            dn_frac = 1.0 - dmask.mean()

        stage("train")
        # local training (vmapped cohort; per-client effective params
        # under downlink loss, the shared broadcast otherwise)
        if algo == "scaffold":
            c_global = unflatten_like(state.c_global, params)

            def loc(p, x, y, ci_vec):
                ci = unflatten_like(ci_vec, params)
                return cu.scaffold_local(p, x, y, c_global, ci, hyper)

            if use_down:
                uploads, aux = jax.vmap(
                    lambda pv, x, y, ci_vec: loc(
                        unflatten_like(pv, params), x, y, ci_vec),
                    in_axes=(0, 0, 0, 0))(eff_vec, X, Y,
                                          state.c_i[ids])
            else:
                uploads, aux = jax.vmap(loc, in_axes=(None, 0, 0, 0))(
                    params, X, Y, state.c_i[ids])
            dw = flatten_clients(uploads["dw"], C)
            dc = flatten_clients(uploads["dc"], C)
            flat = jnp.concatenate([dw, dc], axis=1)         # (C, 2D)
        else:
            if use_down:
                uploads, aux = jax.vmap(
                    lambda pv, x, y: local(
                        unflatten_like(pv, params), x, y, hyper),
                    in_axes=(0, 0, 0))(eff_vec, X, Y)
            else:
                uploads, aux = jax.vmap(
                    lambda p, x, y: local(p, x, y, hyper),
                    in_axes=(None, 0, 0))(params, X, Y)
            flat = flatten_clients(uploads, C)               # (C, D)

        stage("faults")
        # client-level fault injection (repro/netsim/faults.py): what
        # the cohort actually UPLOADS — echo replays of the previous
        # genuine update, sign flips, NaN device failures. Drawn from a
        # separate fold of the round key (FAULT_FOLD), so the base
        # engine's selection/batch/TRA draws are untouched; zero rates
        # pass ``flat`` through bitwise.
        flat_clean = flat
        if use_faults:
            fkey = jax.random.fold_in(key, faults_mod.FAULT_FOLD)
            flat = faults_mod.inject_client_faults(
                fkey, flat, state.echo_mem[ids],
                fail_rate=ctx.f_fail, flip_rate=ctx.f_flip,
                echo_rate=ctx.f_echo)

        stage("uplink")
        # TRA uplink: EF re-inject, lossy-upload mask, per-mode debias
        # aggregation, the new EF memory rows and (q-FedAvg) the masked
        # squared norms — ONE pass over the (C, P, F) uploads through
        # the kernels/uplink_fused megakernel ops (compiled Pallas on
        # TPU; the bit-identical jnp reference elsewhere).
        pad = P * F - D_up
        xp = jnp.pad(flat, ((0, 0), (0, pad))).reshape(C, P, F)
        # nominal drop rate: scalar (broadcast, the pre-netsim special
        # case) or the per-client exponential trace fit gathered for
        # the cohort (tra.per_client_loss)
        lr_c = ctx.loss_rate if ctx.loss_rate.ndim == 0 \
            else ctx.loss_rate[ids]
        lr_col = lr_c if lr_c.ndim == 0 else lr_c[:, None]
        net_channel, net_logbw = state.net.channel, state.net.logbw

        # recovery-policy family: applied to the CHANNEL mask (before
        # the sufficiency override — sufficient clients retransmit and
        # are all-ones regardless). All three policies are computed and
        # mixed by a 0/1 one-hot; ``1*x + 0*y + 0*z == x`` bitwise for
        # finite masks, so the one_shot cell of a traced grid equals
        # the untraced one_shot program with the same draw totals, and
        # the controller can pick per-CLIENT policies from the same
        # expression.
        rec_oh = None       # (C, n_pol) per-client policy one-hot
        realized_c = None   # (C,) realized pre-recovery loss
        fec_frac = arq_frac = None

        def _apply_recovery(base_mask):
            par_mask = rec_mod.fec_parity_mask(u_par, lr_col)
            mask_fec = fec_ops.fec_recover(base_mask, par_mask,
                                           group=rec_group)
            mask_arq = rec_mod.arq_residual_mask(
                base_mask, u_arq, lr_col, ctx.rec_retries)
            oh = bud_mod.controller_policy_onehot(
                state.bud_level[ids]) if use_bud \
                else jnp.broadcast_to(ctx.rec_policy[None, :],
                                      (C, n_pol))
            mask_eff = oh[:, 0:1] * base_mask \
                + oh[:, 1:2] * mask_fec + oh[:, 2:3] * mask_arq
            stats = (oh, 1.0 - base_mask.mean(axis=1),
                     (mask_fec - base_mask).mean(),
                     (mask_arq - base_mask).mean())
            return mask_eff, stats

        if use_ge:
            # bursty loss: advance each cohort client's two-state
            # channel by P packet-steps (kernels/netsim_mask; Pallas
            # on TPU, jnp scan reference elsewhere) and scatter the
            # final states back into the carry. Sufficient clients
            # retransmit — their mask is all-ones — but their channel
            # still advances (the link fades either way).
            p_gb, p_bg = ge_transition_probs(
                lr_c, ctx.burst_len, ctx.good_loss, ctx.bad_loss)
            ge_mask, s_fin = netsim_ops.ge_packet_mask(
                u_tra, u_emit, net_channel[ids], p_gb, p_bg,
                ctx.good_loss, ctx.bad_loss)
            net_channel = net_channel.at[ids].set(s_fin)
            if use_rec:
                ge_mask, (rec_oh, realized_c, fec_frac, arq_frac) = \
                    _apply_recovery(ge_mask)
            pkt_mask = jnp.where(suff.astype(bool)[:, None], 1.0,
                                 ge_mask)
        elif tra_cfg.enabled and use_rec:
            chan_mask = (u_tra >= lr_col).astype(jnp.float32)
            mask_eff, (rec_oh, realized_c, fec_frac, arq_frac) = \
                _apply_recovery(chan_mask)
            pkt_mask = jnp.where(suff.astype(bool)[:, None], 1.0,
                                 mask_eff)
        elif tra_cfg.enabled:
            lost = (u_tra < lr_col) \
                & ~suff.astype(bool)[:, None]
            pkt_mask = 1.0 - lost.astype(jnp.float32)
        else:
            pkt_mask = jnp.ones((C, P))

        # debias rate: once recovery is compiled in, the group_rate
        # estimator must divide by the POST-recovery residual rate
        # (policy-mixed closed form) — correcting by the raw channel
        # rate after ARQ repaired most losses over-inflates every
        # insufficient client by 1/(1-r) and diverges. one_shot rows
        # mix to exactly r, so that cell keeps the legacy estimator.
        lr_deb = lr_c if not use_rec else rec_mod.residual_rate_mixed(
            rec_oh, lr_c, ctx.rec_retries, rec_group)

        if use_bw:
            # time passes for every client, not just the cohort: one
            # AR(1) step on all N log-bandwidth levels per round
            net_logbw = logbw_round_step(key, net_logbw, ctx.bw_rho)
        # server mode: how arrival times fold into this round. The
        # loss-channel-only mask is kept separate (``loss_mask``)
        # because the async buffer stores loss-masked late uploads.
        loss_mask = pkt_mask
        a_c = None          # per-client arrival weight on w_agg
        arrival = None      # logged effective arrival weight (C,)
        lateness = None     # rounds late (staleness memory + buffer)
        if use_dl:
            # arrival times: current bandwidth + packets sent
            # (retransmitters push ~P/(1-r), TRA one-shots push P)
            retransmit = suff.astype(bool) if tra_cfg.enabled \
                else jnp.ones((C,), bool)
            if use_rec:
                # each policy pays its airtime: FEC ships 1 + 1/G
                # model-equivalents, ARQ the expected retry traffic;
                # retransmitters (sufficient clients) still pay the
                # legacy P/(1-r) regardless of policy.
                sends_pol = rec_oh[:, 0] * 1.0 \
                    + rec_oh[:, 1] * rec_mod.fec_sends(rec_group) \
                    + rec_oh[:, 2] * rec_mod.arq_sends(
                        lr_c, ctx.rec_retries, ctx.rec_backoff)
                secs = rec_mod.recovery_upload_seconds(
                    P, F, jnp.exp(net_logbw[ids]), lr_c, retransmit,
                    sends_pol)
            else:
                secs = round_upload_seconds(
                    P, F, jnp.exp(net_logbw[ids]), lr_c, retransmit)
            delivered = deadline_delivered(secs, ctx.deadline_s)
            if need_stale or nonsync or tele_on:
                lateness = arrival_lateness(secs, ctx.deadline_s)
            if not nonsync:
                # sync: a miss drops the WHOLE upload (row of zeros —
                # EF captures it when enabled); the straggler's weight
                # still enters the denominator, biasing the round the
                # way real federated deadlines do. Expression order is
                # the PR-4 one, bitwise (frozen-step lock).
                pkt_mask = pkt_mask * delivered[:, None]
                arrival = delivered
            else:
                ontime = delivered
                late = 1.0 - ontime
                # semi_sync: within-grace stragglers land THIS round,
                # discounted by the fractional staleness past the
                # deadline; beyond-grace misses drop (sync semantics)
                # but their weight leaves the denominator too.
                within = jnp.where(
                    ctx.deadline_s > 0.0,
                    deadline_delivered(secs,
                                       ctx.deadline_s + ctx.grace_s),
                    0.0)
                a_semi = ontime + late * within * \
                    async_mod.staleness_weight(
                        grace_staleness(secs, ctx.deadline_s),
                        ctx.stale_alpha)
                # async: on-time uploads aggregate now; late uploads
                # buffer and land w(tau)-discounted tau rounds later.
                # Infeasible uploads (lateness pinned at MAX_LATENESS)
                # are never buffered, so the arrival log reports them
                # as 0, not as the discount they would never receive.
                feasible = (lateness < MAX_LATENESS).astype(jnp.float32)
                w_late = async_mod.staleness_weight(lateness,
                                                    ctx.stale_alpha)
                a_async_log = ontime + late * feasible * w_late
                if traced_srv:
                    is_sync = ctx.srv_mode[0] > 0.5
                    is_semi = ctx.srv_mode[1] > 0.5
                    is_async = ctx.srv_mode[2] > 0.5
                    # per-mode selection by where() keeps each cell
                    # bitwise equal to its static-mode program (the
                    # selected branch is the unchanged expression)
                    pkt_mask = jnp.where(
                        is_sync, loss_mask * delivered[:, None],
                        jnp.where(is_semi,
                                  loss_mask * within[:, None],
                                  loss_mask))
                    a_c = jnp.where(
                        is_sync, jnp.ones((C,), jnp.float32),
                        jnp.where(is_semi, a_semi, ontime))
                    arrival = jnp.where(
                        is_sync, delivered,
                        jnp.where(is_semi, a_semi, a_async_log))
                elif srv_mode == "semi_sync":
                    pkt_mask = loss_mask * within[:, None]
                    a_c = a_semi
                    arrival = a_semi
                else:  # async
                    a_c = ontime
                    arrival = a_async_log

        # packet-level fault injection: damage in flight, applied to
        # the packets the channel/deadline actually DELIVERS (a lost
        # packet never reaches the server, so EF recycling stays
        # clean). Zero rates pass ``xp`` through bitwise.
        if use_faults:
            xp = faults_mod.inject_packet_faults(
                fkey, xp, pkt_mask, corrupt_rate=ctx.f_corrupt,
                corrupt_scale=ctx.f_cscale,
                bitflip_rate=ctx.f_bitflip)

        kept = None
        if debias == "per_client_rate" and not use_faults:
            # coordinate-weighted kept fraction (last packet partial);
            # the fault path computes this from the SCREENED mask
            # inside robust_uplink_round instead
            pcnt = jnp.full((P,), F, jnp.float32).at[-1].set(F - pad)
            kept = (pkt_mask @ pcnt) / D_up

        # aggregation weights per algorithm (q-FedAvg scales clients by
        # F_k^q outside the denominator and needs the masked norms)
        if algo == "qfedavg":
            eps = 1e-10
            fq = jnp.power(aux["loss0"] + eps, cfg.q)
            w_agg, mult, want_ssq = jnp.ones(C), fq, True
        elif algo == "afl":
            w_agg, mult, want_ssq = state.lam[ids], None, False
        else:
            w_agg, mult, want_ssq = weights, None, False
        # gradient_norm selection scores next round's cohort by the
        # masked norms the megakernel computes in this same pass; the
        # loss-budget controller reads the same norms as its
        # divergence signal
        want_ssq = want_ssq or need_gnorm or use_bud
        # non-sync modes fold the arrival weight into the aggregation
        # weights: zero-weight stragglers leave BOTH the numerator and
        # the denominator (the EF update and ssq are weight-free in
        # the kernel, so a buffered late upload is not double-counted
        # through EF). a_c is None on the pure-sync path — no
        # multiply, bitwise legacy.
        w_up = w_agg if a_c is None else w_agg * a_c

        if use_faults:
            # defended uplink (kernels/robust_agg): finite-screen
            # quarantine (bad packets become AS IF LOST — same debias
            # machinery), norm clip, trimmed mean — every gate traced,
            # off-gates bitwise the undefended expressions below.
            rob = robust_ops.robust_uplink_round(
                xp, pkt_mask, w_up, mode=debias, d_up=D_up,
                screen=ctx.d_screen, clip_norm=ctx.d_clip,
                trim_gate=ctx.d_trim, trim_k=trim_k,
                ef_rows=state.ef_mem[ids] if ef else None,
                sufficient=suff, loss_rate=lr_deb, mult=mult,
                want_ssq=want_ssq)
            agg, new_ef_rows, ssq = rob.agg, rob.ef_rows, rob.ssq
            kept = rob.kept
        else:
            rob = None
            agg, new_ef_rows, ssq = uplink_ops.uplink_round(
                xp, pkt_mask, w_up, mode=debias, d_up=D_up,
                ef_rows=state.ef_mem[ids] if ef else None, kept=kept,
                sufficient=suff, loss_rate=lr_deb, mult=mult,
                want_ssq=want_ssq)
        new_ef = state.ef_mem.at[ids].set(new_ef_rows) if ef \
            else state.ef_mem

        stage("server")
        # async arrival buffer: pop entries due this round into the
        # aggregate, push this round's late uploads (core/async_agg.py)
        new_buf = state.buf
        den_ready = None
        if use_buf:
            t_f = t.astype(jnp.float32)
            num_ready, den_ready, popped = async_mod.buffer_pop_ready(
                state.buf, t_f, ctx.stale_alpha)
            # recombine: the kernel's aggregate is num/den with the
            # scalar den = max(sum w_up, eps); ready buffered entries
            # extend both sides, each staleness-discounted. When
            # nothing is due, keep the kernel output bitwise (the
            # recombination would round-trip num/den through a
            # multiply).
            den_on = w_up.sum()
            num_on = agg * jnp.maximum(den_on, DENOM_EPS)
            agg_buf = (num_on + num_ready) \
                / jnp.maximum(den_on + den_ready, DENOM_EPS)
            use_ready = den_ready > 0.0
            if traced_srv:
                use_ready = use_ready & is_async
            agg = jnp.where(use_ready, agg_buf, agg)
            # in-flight candidates: the debias-scaled loss-masked
            # upload (the SAME per-client scale the kernel applies to
            # on-time clients), due ``lateness`` rounds from now.
            # Never-arriving uploads (lateness pinned at MAX_LATENESS
            # by a degenerate deadline/bandwidth) stay out rather than
            # occupying slots.
            q_full = uplink_ops.debias_client_scale(
                w_agg, mode=debias, kept=kept, sufficient=suff,
                loss_rate=lr_deb, mult=mult)
            coord_mask = jnp.repeat(loss_mask, F, axis=1)[:, :D_up]
            base_rows = flat + state.ef_mem[ids] if ef else flat
            if use_faults:
                # the buffer refuses to launder corrupted data: the
                # norm clip applies to buffered contributions too, a
                # quarantined arrival (any bad delivered packet) is
                # refused outright, and candidates are sanitised so a
                # NaN in a LOST packet cannot ride contrib through
                # coord_mask * 0 (NaN * 0 = NaN). All behind the
                # traced screen/clip gates — off-gates stay bitwise.
                scr_on = ctx.d_screen > 0.5
                q_full = q_full * rob.s_clip
                base_rows = jnp.where(
                    scr_on & ~jnp.isfinite(base_rows), 0.0, base_rows)
            contrib = base_rows * coord_mask * q_full[:, None]
            cand_live = (lateness > 0.0) & (lateness < MAX_LATENESS)
            if use_faults:
                cand_live = cand_live & ~(scr_on & (rob.qcnt > 0.0))
            if traced_srv:
                cand_live = cand_live & is_async
            new_buf = async_mod.buffer_insert(
                popped, contrib, t_f + lateness, w_agg, lateness,
                cand_live)

        # server update per algorithm
        c_global_new, c_i_new, lam_new = \
            state.c_global, state.c_i, state.lam
        if algo == "scaffold":
            D = dw.shape[1]
            dw_agg, dc_agg = agg[:D], agg[D:]
            new_vec = old_vec + dw_agg
            c_global_new = state.c_global + (C / N) * dc_agg
            c_i_new = state.c_i.at[ids].set(state.c_i[ids] + dc)
        elif algo == "qfedavg":
            # delta_k = F_k^q dw_k;  h_k = q F^(q-1)||dw||^2 + L F^q
            h = cfg.q * jnp.power(aux["loss0"] + eps, cfg.q - 1) \
                * ssq + cfg.lipschitz * fq
            # debiased SUM of deltas = debiased mean * C
            agg_sum = agg * C
            new_vec = old_vec - agg_sum / jnp.maximum(h.sum(), 1e-8)
        elif algo == "afl":
            new_vec = agg
        elif algo == "pfedme":
            new_vec = (1 - cfg.pfedme_beta) * old_vec \
                + cfg.pfedme_beta * agg
        else:  # fedavg / perfedavg: weighted mean of uploaded models
            new_vec = agg
        if nonsync:
            # empty server step (no on-time, no grace, nothing due
            # from the buffer): the update is the identity, never a
            # division-by-zero and never a zeroed model (fedavg's
            # aggregate is a mean of MODELS). Sync keeps its legacy
            # all-stragglers behaviour — that collapse is the
            # documented baseline the async modes fix.
            den_tot = w_up.sum() if den_ready is None \
                else w_up.sum() + den_ready
            has_arrivals = den_tot > 0.0
            if traced_srv:
                has_arrivals = has_arrivals | is_sync
            new_vec = jnp.where(has_arrivals, new_vec, old_vec)
        new_params = unflatten_like(new_vec, params)

        if algo == "afl":
            # projected gradient ascent on client losses (minimax),
            # on the staged data with a padding mask
            Xe = dd.train_x[ids, :afl_len]
            Ye = dd.train_y[ids, :afl_len]
            msk = (jnp.arange(afl_len)[None, :]
                   < counts[:, None]).astype(jnp.float32)
            losses = jax.vmap(mlp_weighted_loss,
                              in_axes=(None, 0, 0, 0))(
                new_params, Xe, Ye, msk)
            lam = state.lam.at[ids].add(cfg.afl_lr_lambda * losses)
            lam = jnp.maximum(lam, 0.0)
            lam_new = lam / lam.sum()

        # selection score memory: scatter this round's cohort stats for
        # the NEXT round's gradient_norm / loss_aware scores
        gnorm_new = state.gnorm_mem.at[ids].set(ssq) if need_gnorm \
            else state.gnorm_mem
        loss_new = state.loss_mem.at[ids].set(aux["loss0"]) \
            if need_loss else state.loss_mem
        stale_new = state.stale_mem.at[ids].set(lateness) \
            if need_stale and use_dl else state.stale_mem
        # fault-model memories: the echo memory records what each
        # client GENUINELY computed (the replay source), the
        # reputation memory accumulates this round's quarantined
        # fraction for the reputation_aware policy
        echo_new = state.echo_mem.at[ids].set(flat_clean) \
            if use_faults else state.echo_mem
        rep_new = state.rep_mem.at[ids].add(rob.qcnt / P) \
            if need_rep else state.rep_mem

        # stale-parameter fallback memory: after this round, the
        # client's local model IS eff_vec (received coords fresh, lost
        # coords whatever it already had) — that's what a re-selected
        # client resumes from next time its downlink drops.
        stale_model_new = state.stale_model.at[ids].set(eff_vec) \
            if (use_down and down_stale) else state.stale_model
        # adaptive loss-budget controller: close the loop on the
        # REALIZED pre-recovery loss and the update-norm divergence
        # signal. The per-client policy used THIS round was read from
        # bud_level before the update (clients commit a policy before
        # the channel reveals itself); the EMA/level written here
        # drives the NEXT selection of this client.
        bud_level_new, bud_loss_new = state.bud_level, state.bud_loss
        n_esc = lv = None
        if use_bud:
            lv, ema_new, n_esc = bud_mod.controller_update(
                state.bud_level[ids], state.bud_loss[ids], realized_c,
                ssq, budget=ctx.bud_budget, beta=ctx.bud_ema,
                div_gate=ctx.bud_div)
            bud_level_new = state.bud_level.at[ids].set(lv)
            bud_loss_new = state.bud_loss.at[ids].set(ema_new)

        stage("telemetry")
        logs = {"loss": aux["loss0"].mean(), "ids": ids}
        if use_faults:
            # per-cohort-slot quarantined-packet counts — the
            # robustness analyses' observability signal
            logs["quarantine"] = rob.qcnt
        if use_dl:
            # effective per-cohort-slot arrival weight (1 = landed on
            # time at full weight, 0 = dropped): the participation
            # signal the fairness analyses read.
            logs["arrival"] = arrival
        # device-resident telemetry (core/telemetry.py): per-round
        # scalars / compact aggregates join the scan outputs under
        # "tele/..." keys, and at level="full" the cumulative
        # per-client aggregates update in the carry. Reads only
        # signals the round already computed — never the math.
        new_tele = state.tele
        if tele_on:
            tele_scale = uplink_ops.debias_client_scale(
                w_agg, mode=debias, kept=kept, sufficient=suff,
                loss_rate=lr_deb, mult=mult)
            tlogs, new_tele = tele_mod.round_telemetry(
                tele_cfg, state.tele, ids=ids, n_clients=N,
                pkt_mask=pkt_mask, loss_mask=loss_mask,
                old_vec=old_vec, new_vec=new_vec, scale=tele_scale,
                logbw=ctx.sel_logbw
                if ctx.sel_logbw.shape[0] == N else None,
                ef_new_rows=new_ef_rows if ef else None,
                arrival=arrival if use_dl else None,
                lateness=lateness if use_dl else None,
                qcnt=rob.qcnt if use_faults else None,
                buf_due=new_buf.due if use_buf else None,
                buf_empty_due=async_mod.EMPTY_DUE,
                down_frac=dn_frac,
                fec_frac=fec_frac, arq_frac=arq_frac,
                bud_escal=n_esc,
                bud_level=lv.mean() if use_bud else None)
            logs.update(tlogs)
        new_state = EngineState(new_params, new_ef, c_global_new,
                                c_i_new, lam_new,
                                NetSimState(net_channel, net_logbw,
                                            net_down),
                                gnorm_new, loss_new, stale_new,
                                new_buf, echo_new, rep_new, new_tele,
                                stale_model_new, bud_level_new,
                                bud_loss_new)
        return new_state, logs

    return step


class RoundScanEngine:
    """Round-scan executor for one (config, dataset, network) scenario.

    The engine is stateless between calls: callers own the
    ``EngineState`` and thread it through ``run_block`` / ``run_single``,
    which is how state survives block boundaries by construction. The
    passed-in state is DONATED — callers must use the returned state and
    drop the old reference (which every call site already does).
    """

    def __init__(self, cfg, data, sufficient: np.ndarray,
                 eligible: np.ndarray,
                 device_data: Optional[DeviceDataset] = None, *,
                 upload_mbps: Optional[np.ndarray] = None,
                 packet_loss: Optional[np.ndarray] = None):
        if cfg.algo not in ENGINE_ALGOS:
            raise ValueError(f"unsupported algo {cfg.algo!r}")
        self.cfg = cfg
        self.dd = device_data if device_data is not None \
            else stage_on_device(data)
        self.n_clients = int(self.dd.counts.shape[0])
        n_eligible = int(np.asarray(eligible).sum())
        if n_eligible == 0:
            raise ValueError("no eligible clients")
        self.cohort = min(cfg.clients_per_round, n_eligible)
        self.eligible = jnp.asarray(np.asarray(eligible, bool))
        self.sufficient = jnp.asarray(
            np.asarray(sufficient, np.float32))
        if cfg.tra.per_client_loss:
            if packet_loss is None:
                raise ValueError("tra.per_client_loss needs the trace "
                                 "draw (pass nets.packet_loss)")
            loss_rate = jnp.asarray(np.asarray(packet_loss, np.float32))
        else:
            loss_rate = jnp.float32(cfg.tra.loss_rate)
        if (cfg.netsim.bw_ar1 or cfg.netsim.deadline) \
                and upload_mbps is None:
            raise ValueError("netsim bandwidth/deadline models need "
                             "the trace draw (pass nets.upload_mbps)")
        if (cfg.sel.traced or cfg.sel.policy == "bandwidth_threshold") \
                and upload_mbps is None:
            raise ValueError(
                "the bandwidth_threshold selection score (and the "
                "traced policy family, which includes it) needs the "
                "trace draw (pass nets.upload_mbps)")
        self._upload_mbps = None if upload_mbps is None \
            else np.asarray(upload_mbps, np.float32)
        ns = cfg.netsim
        sel = cfg.sel
        srv = cfg.srv
        flt = cfg.faults
        dfn = cfg.defense
        self.ctx = ScenarioCtx(
            base_key=jax.random.PRNGKey(cfg.seed),
            loss_rate=loss_rate,
            eligible=self.eligible,
            sufficient=self.sufficient,
            data=self.dd,
            burst_len=jnp.float32(ns.burst_len),
            good_loss=jnp.float32(ns.good_loss),
            bad_loss=jnp.float32(ns.bad_loss),
            bw_rho=jnp.float32(ns.bw_rho),
            deadline_s=jnp.float32(ns.deadline_s),
            sel_threshold=jnp.float32(sel.threshold_mbps),
            sel_temp=jnp.float32(sel.temperature),
            sel_explore=jnp.float32(sel.explore),
            sel_policy=jnp.asarray(sel_mod.policy_onehot(sel.policy)),
            sel_logbw=log_upload_speeds(self._upload_mbps)
            if self._upload_mbps is not None
            else jnp.zeros((0,), jnp.float32),
            srv_mode=jnp.asarray(async_mod.mode_onehot(srv.mode)),
            stale_alpha=jnp.float32(srv.staleness_alpha),
            grace_s=jnp.float32(srv.grace_s),
            f_corrupt=jnp.float32(flt.corrupt_rate),
            f_cscale=jnp.float32(flt.corrupt_scale),
            f_bitflip=jnp.float32(flt.bitflip_rate),
            f_fail=jnp.float32(flt.fail_rate),
            f_flip=jnp.float32(flt.flip_rate),
            f_echo=jnp.float32(flt.echo_rate),
            d_screen=jnp.float32(1.0 if dfn.screen else 0.0),
            d_clip=jnp.float32(faults_mod.clip_knob(dfn)),
            d_trim=jnp.float32(1.0 if dfn.trim else 0.0),
            down_loss=jnp.float32(ns.down_loss),
            down_deadline_s=jnp.float32(ns.down_deadline_s),
            rec_policy=jnp.asarray(
                rec_mod.recovery_onehot(cfg.recovery.policy)),
            rec_retries=jnp.float32(cfg.recovery.retries),
            rec_backoff=jnp.float32(cfg.recovery.backoff),
            bud_budget=jnp.float32(cfg.lossbudget.budget),
            bud_ema=jnp.float32(cfg.lossbudget.ema),
            bud_div=jnp.float32(cfg.lossbudget.div_gate))
        self._step, self._single, self._block = _cached_jits(
            cfg, self.cohort)

    # -- state --------------------------------------------------------------
    def init_state(self, params) -> EngineState:
        return init_engine_state(self.cfg, params, self.n_clients,
                                 base_key=self.ctx.base_key,
                                 loss_rate=self.ctx.loss_rate,
                                 upload_mbps=self._upload_mbps)

    # -- execution ----------------------------------------------------------
    def run_single(self, state: EngineState, t: int
                   ) -> Tuple[EngineState, Dict[str, jnp.ndarray]]:
        """One round at absolute index ``t`` (the reference path)."""
        return self._single(self.ctx, state, jnp.asarray(t, jnp.int32))

    def run_block(self, state: EngineState, t0: int, k: int
                  ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Scan rounds [t0, t0+k) in one device program; flush logs to
        host. Returns (state, {"loss": (k,), "ids": (k, C)})."""
        with tele_mod.Span("fl.block"):
            with tele_mod.Span("fl.block.args"):
                ts = jnp.arange(t0, t0 + k, dtype=jnp.int32)
            state, logs = self._block(self.ctx, state, ts)
            # one fetch: every log's copy is queued behind the program
            # before the host waits on any, so the block pays one round
            # trip, not one per log. No wait of its own either: a wait
            # before the copies starts them only once the host has seen
            # the program end (about 0.13 ms a block on a TPU v5e)
            with tele_mod.Span("fl.block.copy"):
                for v in logs.values():
                    v.copy_to_host_async()
                out = {k_: np.asarray(v) for k_, v in logs.items()}
            self._block.count(d2h=sum(v.nbytes for v in out.values()),
                              arrays=len(out))
        return state, out
