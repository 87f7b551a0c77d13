"""END-TO-END DRIVER: loss-tolerant federated training of a transformer.

The paper's protocol integrated as a first-class feature of the
production training step:

  * the client cohort rides the ``data`` mesh axis — each data-parallel
    group simulates one client holding a full (tensor-parallel) model
    replica;
  * per-client gradients come from ``vmap(grad)`` over the client axis;
  * each *insufficient* client's upload is packet-masked (per-leaf packets,
    256 f32 coords each — the TRA "throw" step);
  * aggregation is the debiased masked mean (kernels/tra_agg math) — i.e.
    the cross-client collective IS the paper's Eq. (1), executed by GSPMD
    as masked psums over the data/pod axes;
  * the optimizer consumes the debiased aggregate.

``python -m repro.launch.fl_train --arch stablelm-3b --reduced`` runs a
CPU-sized cohort end-to-end (a few hundred steps: see examples/).
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig, get_config
from repro.core import telemetry as tele_mod
from repro.core.tra import TRAConfig
from repro.launch.train import synth_batch
from repro.utils.events import EventWriter, RoundRecord, fingerprint_of
from repro.models import transformer as tf
from repro.optim.optimizers import (apply_updates, clip_by_global_norm,
                                    make_optimizer)
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.guards import assert_finite_tree
from repro.utils.shardctx import shard


def _leaf_packet_mask(key, shape, loss_rate, packet_floats: int):
    """Per-packet Bernoulli keep mask broadcast to a leaf's shape."""
    n = int(np.prod(shape))
    P = -(-n // packet_floats)
    m = (jax.random.uniform(key, (P,)) >= loss_rate).astype(jnp.float32)
    flat = jnp.repeat(m, packet_floats)[:n]
    return flat.reshape(shape)


def make_fl_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                       tra: TRAConfig, n_clients: int):
    """Returns (fl_step, opt). Batch leaves carry a leading client axis C.

    ``loss_rate`` is an optional traced override of ``tra.loss_rate`` —
    pass a scalar array to make the drop rate a scenario-varying input
    (what ``make_fl_sweep_step`` vmaps over); omit it for the classic
    single-scenario closure-constant behaviour."""
    opt = make_optimizer(tcfg.optimizer, tcfg.lr, momentum=tcfg.momentum,
                         weight_decay=tcfg.weight_decay)
    remat = tcfg.remat != "none"

    def fl_step(params, opt_state, batch, sufficient, key, loss_rate=None,
                participating=None):
        rate = tra.loss_rate if loss_rate is None else loss_rate
        # --- thread Client: local gradient computation ------------------
        def client_loss(p, b):
            loss, _ = tf.forward(cfg, p, b, remat=remat)
            return loss

        losses, grads = jax.vmap(
            jax.value_and_grad(client_loss), in_axes=(None, 0))(params, batch)
        # grads: pytree with leading client axis C (sharded over data)

        # per-client squared update norms |g_i|^2 — the gradient_norm
        # selection policy's score input (the engine path gets this from
        # the megakernel's ssq output; here it is a cheap metrics pass)
        client_ssq = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)),
                    axis=tuple(range(1, g.ndim)))
            for g in jax.tree_util.tree_leaves(grads))

        # --- TRA upload + debiased aggregation (Eq. 1 family) -----------
        # ``participating`` (C,) f32 cohort mask: non-members contribute
        # nothing and the mean runs over the cohort size. None (the
        # default) keeps the everyone-participates math bitwise intact.
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        keys = jax.random.split(key, len(leaves) * n_clients).reshape(
            len(leaves), n_clients, 2)
        agg_leaves = []
        for li, g in enumerate(leaves):
            lf_shape = g.shape[1:]
            masks = jax.vmap(
                lambda kc, s: _leaf_packet_mask(kc, lf_shape, rate,
                                                tra.packet_floats),
                in_axes=(0, None))(keys[li], 0)
            # sufficient clients retransmit -> full delivery
            suff = sufficient.reshape((n_clients,) + (1,) * len(lf_shape))
            masks = jnp.maximum(masks, suff.astype(masks.dtype))
            if participating is not None:
                part = participating.reshape(
                    (n_clients,) + (1,) * len(lf_shape))
                masks = masks * part
                denom = jnp.maximum(participating.sum(), 1.0)
            gm = g * masks.astype(g.dtype)
            if tra.debias == "per_coord_count":
                num = (gm.astype(jnp.float32) * masks).sum(0)
                den = jnp.maximum(masks.sum(0), 1e-9)
                agg = num / den
            elif tra.debias == "group_rate":   # paper Eq. (1), corrected
                scale = jnp.where(suff.astype(bool), 1.0,
                                  1.0 / jnp.maximum(1.0 - rate, 1e-6))
                gs = gm.astype(jnp.float32) * scale
                agg = gs.sum(0) / denom if participating is not None \
                    else gs.mean(0)
            else:                              # "none": biased mean
                gf = gm.astype(jnp.float32)
                agg = gf.sum(0) / denom if participating is not None \
                    else gf.mean(0)
            agg_leaves.append(agg.astype(g.dtype))
        agg_grads = jax.tree_util.tree_unflatten(treedef, agg_leaves)

        # --- thread Server: optimizer update ----------------------------
        if tcfg.grad_clip > 0:
            agg_grads, gnorm = clip_by_global_norm(agg_grads, tcfg.grad_clip)
        else:
            gnorm = jnp.float32(0.0)
        updates, opt_state = opt.update(agg_grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {"loss": losses.mean(), "client_losses": losses,
                   "grad_norm": gnorm, "client_grad_ssq": client_ssq}
        return params, opt_state, metrics

    return fl_step, opt


def make_fl_contrib_step(cfg: ModelConfig, tcfg: TrainConfig,
                         tra: TRAConfig, n_clients: int):
    """The async-server decomposition of ``make_fl_train_step``:

    ``contrib_step(params, batch, sufficient, key)`` returns the
    per-client debias-SCALED masked gradient contributions (pytree with
    leading client axis C, f32) plus per-client losses — i.e. the
    numerator terms of the aggregate, BEFORE the cross-client mean. The
    host decides which contributions land this round (on-time), which
    wait in the arrival buffer (late, ``--server-mode async``) and with
    what staleness weight, then calls
    ``apply_step(params, opt_state, num, den)`` with the recombined
    numerator/denominator. Splitting numerator from denominator is what
    lets buffered arrivals merge rounds later without re-running the
    clients. Only ``group_rate``/``none`` debias is supported: the
    per-coord-count denominator is a full gradient-shaped pytree and is
    refused (same restriction as the engine's buffer path).
    """
    if tra.debias == "per_coord_count":
        raise ValueError("per_coord_count debias has a per-coordinate "
                         "denominator and cannot ride the scalar-weight "
                         "arrival buffer; use group_rate or none")
    opt = make_optimizer(tcfg.optimizer, tcfg.lr, momentum=tcfg.momentum,
                         weight_decay=tcfg.weight_decay)
    remat = tcfg.remat != "none"

    def contrib_step(params, batch, sufficient, key):
        rate = tra.loss_rate

        def client_loss(p, b):
            loss, _ = tf.forward(cfg, p, b, remat=remat)
            return loss

        losses, grads = jax.vmap(
            jax.value_and_grad(client_loss), in_axes=(None, 0))(params, batch)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        keys = jax.random.split(key, len(leaves) * n_clients).reshape(
            len(leaves), n_clients, 2)
        out = []
        for li, g in enumerate(leaves):
            lf_shape = g.shape[1:]
            masks = jax.vmap(
                lambda kc, s: _leaf_packet_mask(kc, lf_shape, rate,
                                                tra.packet_floats),
                in_axes=(0, None))(keys[li], 0)
            suff = sufficient.reshape((n_clients,) + (1,) * len(lf_shape))
            masks = jnp.maximum(masks, suff.astype(masks.dtype))
            gm = (g * masks.astype(g.dtype)).astype(jnp.float32)
            if tra.debias == "group_rate":
                scale = jnp.where(suff.astype(bool), 1.0,
                                  1.0 / jnp.maximum(1.0 - rate, 1e-6))
                gm = gm * scale
            out.append(gm)
        return jax.tree_util.tree_unflatten(treedef, out), losses

    def apply_step(params, opt_state, num, den):
        agg_grads = jax.tree.map(
            lambda n, p: (n / den).astype(p.dtype), num, params)
        if tcfg.grad_clip > 0:
            agg_grads, gnorm = clip_by_global_norm(agg_grads, tcfg.grad_clip)
        else:
            gnorm = jnp.float32(0.0)
        updates, opt_state = opt.update(agg_grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, gnorm

    return contrib_step, apply_step, opt


def make_fl_sweep_step(cfg: ModelConfig, tcfg: TrainConfig,
                       tra: TRAConfig, n_clients: int):
    """Scenario-vectorized FL step: vmap ``fl_step`` over a leading
    scenario axis on (params, opt_state, key, loss_rate), with the batch
    and sufficiency reports shared — so a whole loss-rate grid of the
    transformer FL protocol is one compiled program per step.

    Returns (sweep_step, opt); sweep_step(params_S, opt_state_S, batch,
    sufficient, keys_S, loss_rates_S) -> (params_S, opt_state_S,
    metrics with leading S)."""
    fl_step, opt = make_fl_train_step(cfg, tcfg, tra, n_clients)
    sweep_step = jax.vmap(
        lambda p, o, b, s, k, r: fl_step(p, o, b, s, k, r),
        in_axes=(0, 0, None, None, 0, 0))
    return sweep_step, opt


def _run_sweep(cfg, tcfg, tra, args, rates):
    """Grid route: one model replica per TRA loss rate, all trained by a
    single vmapped step — the transformer-scale analogue of
    core/sweep.SweepEngine (scenario axis = loss rate here; seeds via
    per-scenario keys)."""
    S, C = len(rates), args.clients
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    sweep_step, opt = make_fl_sweep_step(cfg, tcfg, tra, C)
    opt_state = opt.init(params)

    def stack(tree):
        return jax.tree.map(lambda x: jnp.stack([x] * S), tree)

    params_s, opt_s = stack(params), stack(opt_state)
    sweep_step = _timed(jax.jit(sweep_step), "sweep", args)
    loss_rates = jnp.asarray(rates, jnp.float32)
    sufficient = jnp.asarray(
        [0.0] * args.insufficient + [1.0] * (C - args.insufficient))
    rng = np.random.default_rng(0)
    writer = _open_writer(args, "sweep")
    try:
        for i in range(args.steps):
            batches = [synth_batch(cfg, args.batch, args.seq, rng)
                       for _ in range(C)]
            batch = {k: jnp.stack([b[k] for b in batches])
                     for k in batches[0]}
            keys = jnp.stack([jax.random.PRNGKey(1000 + i + 7919 * s)
                              for s in range(S)])
            t0 = time.time()
            params_s, opt_s, m = sweep_step(params_s, opt_s, batch,
                                            sufficient, keys, loss_rates)
            losses = np.asarray(m["loss"])
            per = " ".join(f"r={r:.2f}:{l:8.4f}"
                           for r, l in zip(rates, losses))
            print(f"round {i:4d} {per} ({time.time()-t0:.2f}s)",
                  flush=True)
            if writer is not None:
                for s in range(S):
                    writer.write_round(RoundRecord(
                        round=i, scenario=s,
                        train_loss=float(losses[s]),
                        realized_loss=float(rates[s])))
            if not np.all(np.isfinite(losses)):
                # fail fast naming the bad scenario/leaf, not loss=nan
                assert_finite_tree(params_s, name=f"round{i}/params")
                assert_finite_tree({"loss": losses}, name=f"round{i}")
    finally:
        if writer is not None:
            writer.write_program_stats(tele_mod.REGISTRY.stats())
            writer.close()
    return 0


def _run_async(cfg, tcfg, tra, args):
    """Host-driven ``--server-mode semi_sync|async`` route: the
    transformer-scale mirror of the engine's arrival buffer. Each round
    every client computes its contribution; the netsim delivery model
    (per-client FCC-trace bandwidth, TRA retransmission inflation)
    decides who beats ``--deadline-s``. Late contributions are buffered
    host-side (``--buffer-k`` earliest-due entries win, deterministic)
    and merged into the round they arrive in with the staleness
    discount w(tau) = (1+tau)^(-alpha); semi_sync instead folds
    within-grace stragglers into the CURRENT round with the fractional
    discount and drops the rest. A round with no arrivals at all leaves
    params untouched (identity, no 0/0)."""
    from repro.core.async_agg import staleness_weight
    from repro.netsim.delivery import (MAX_LATENESS, arrival_lateness,
                                       grace_staleness,
                                       round_upload_seconds)
    from repro.network.trace import sample_networks

    C = args.clients
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    n_pkts = -(-n_params // tra.packet_floats)
    contrib_step, apply_step, opt = make_fl_contrib_step(cfg, tcfg, tra, C)
    opt_state = opt.init(params)
    contrib_step = _timed(jax.jit(contrib_step), "async_contrib", args)
    apply_step = _timed(jax.jit(apply_step), "async_apply", args)
    sufficient = jnp.asarray(
        [0.0] * args.insufficient + [1.0] * (C - args.insufficient))
    mbps = sample_networks(np.random.default_rng(0), C).upload_mbps
    secs = np.asarray(round_upload_seconds(
        n_pkts, tra.packet_floats, jnp.asarray(mbps),
        jnp.float32(args.loss_rate),
        jnp.asarray(sufficient, bool)))                  # (C,) static here
    lateness = np.asarray(arrival_lateness(
        jnp.asarray(secs), jnp.float32(args.deadline_s)))
    alpha = args.staleness_alpha
    buffer = []                  # [(due, w_tau, contrib pytree)] host-side
    rng = np.random.default_rng(0)
    writer = _open_writer(args, "async")
    for i in range(args.steps):
        batches = [synth_batch(cfg, args.batch, args.seq, rng)
                   for _ in range(C)]
        batch = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}
        t0 = time.time()
        contribs, losses = contrib_step(params, batch, sufficient,
                                        jax.random.PRNGKey(1000 + i))
        if args.server_mode == "semi_sync":
            within = secs <= args.deadline_s + args.grace_s
            gtau = np.asarray(grace_staleness(
                jnp.asarray(secs), jnp.float32(args.deadline_s)))
            w_c = np.where(lateness == 0, 1.0,
                           np.where(within,
                                    np.asarray(staleness_weight(
                                        jnp.asarray(gtau),
                                        jnp.float32(alpha))), 0.0))
        else:                                            # async
            w_c = (lateness == 0).astype(np.float32)
        num = jax.tree.map(
            lambda x: jnp.einsum("c,c...->...", jnp.asarray(
                w_c, jnp.float32), x), contribs)
        den = float(w_c.sum())
        ready = [e for e in buffer if e[0] <= i]
        buffer = [e for e in buffer if e[0] > i]
        for due, w_tau, con in ready:
            num = jax.tree.map(lambda n, c: n + w_tau * c, num, con)
            den += w_tau
        if args.server_mode == "async":
            for c in range(C):
                if 0 < lateness[c] < MAX_LATENESS:
                    w_tau = float(staleness_weight(
                        jnp.float32(lateness[c]), jnp.float32(alpha)))
                    buffer.append((i + int(lateness[c]), w_tau,
                                   jax.tree.map(lambda x: x[c], contribs)))
            buffer = sorted(buffer, key=lambda e: e[0])[:args.buffer_k]
        if den > 0:
            params, opt_state, _ = apply_step(params, opt_state, num,
                                              jnp.float32(den))
        print(f"round {i:4d} loss={float(losses.mean()):8.4f} "
              f"ontime={int((lateness == 0).sum())}/{C} "
              f"buffered={len(ready)}->merged den={den:.3f} "
              f"({time.time()-t0:.2f}s)", flush=True)
        if writer is not None:
            writer.write_round(RoundRecord(
                round=i, train_loss=float(losses.mean()),
                arrival_mean=float(np.mean(w_c)),
                buf_fill=len(buffer) / max(args.buffer_k, 1),
                delivered_frac=float((lateness == 0).mean())))
        if not np.isfinite(float(losses.mean())):
            # name the offending leaf (params or the loss itself)
            assert_finite_tree(params, name=f"round{i}/params")
            assert_finite_tree({"loss": losses}, name=f"round{i}")
    if writer is not None:
        writer.write_program_stats(tele_mod.REGISTRY.stats())
        writer.close()
    return 0


def _open_writer(args, route: str):
    """Host-side telemetry writer for the launch routes. The launch
    loops drive jitted steps directly (no scan engine), so records
    carry only the signals the route actually observes — absent fields
    mean "not instrumented here", matching the event-schema contract."""
    if args.telemetry == "off":
        return None
    return EventWriter(
        args.events_out,
        config_fingerprint=fingerprint_of(
            (args.arch, route, args.clients, args.insufficient,
             args.loss_rate, args.debias, args.server_mode)),
        meta={"route": route, "arch": args.arch,
              "n_clients": args.clients, "steps": args.steps,
              "telemetry_level": args.telemetry})


def _timed(fn, route: str, args):
    """Register + wrap a launch-route jitted step in the program-timing
    registry (compile/exec split, same ledger the engine caches use)."""
    if args.telemetry == "off":
        return fn
    fp = tele_mod.REGISTRY.record_lookup(
        "launch", (args.arch, route, args.clients, args.debias,
                   args.server_mode), hit=False)
    return tele_mod.TimedProgram(fn, "launch", fp, "launch")


# Selection policies the host-driven launch loop supports. netsim_state
# is excluded: its score is the engine's device-resident Gilbert–Elliott
# channel state, which this driver does not simulate.
LAUNCH_POLICIES = ("uniform", "bandwidth_threshold", "gradient_norm",
                   "loss_aware")


def _make_selector(args, n_clients: int):
    """Host-side round selector: returns (select, update) closures over
    the per-client score memories, mirroring the engine's
    gnorm_mem/loss_mem carries (select reads the memories as of the
    PREVIOUS round; update scatters this round's cohort metrics)."""
    from repro.core import selection as sel_mod
    from repro.network.trace import log_upload_speeds, sample_networks

    nets = sample_networks(np.random.default_rng(0), n_clients)
    logbw = log_upload_speeds(nets.upload_mbps)
    gnorm_mem = np.zeros(n_clients, np.float32)
    loss_mem = np.zeros(n_clients, np.float32)
    eligible = jnp.ones(n_clients, bool)

    def select(step_idx: int) -> np.ndarray:
        logits = sel_mod.policy_logits(
            args.selection_policy,
            temperature=jnp.float32(args.selection_temperature),
            explore=jnp.float32(0.0),
            threshold_mbps=jnp.float32(2.0),
            logbw=logbw, gnorm_mem=jnp.asarray(gnorm_mem),
            loss_mem=jnp.asarray(loss_mem))
        key = jax.random.fold_in(jax.random.PRNGKey(500), step_idx)
        return np.asarray(sel_mod.select_clients(key, logits, eligible,
                                                 args.cohort))

    def update(ids: np.ndarray, metrics: Dict[str, Any]):
        gnorm_mem[ids] = np.asarray(metrics["client_grad_ssq"])[ids]
        loss_mem[ids] = np.asarray(metrics["client_losses"])[ids]

    return select, update


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--insufficient", type=int, default=1,
                    help="# clients with lossy uploads")
    ap.add_argument("--loss-rate", type=float, default=0.1)
    ap.add_argument("--cohort", type=int, default=None,
                    help="clients selected per round; default: every "
                         "client participates (the legacy path, "
                         "bitwise unchanged)")
    ap.add_argument("--selection-policy", default="uniform",
                    choices=LAUNCH_POLICIES,
                    help="host-driven cohort selection score "
                         "(core/selection.py; netsim_state needs the "
                         "engine's channel state and is engine-only)")
    ap.add_argument("--selection-temperature", type=float, default=1.0)
    ap.add_argument("--server-mode", default="sync",
                    choices=("sync", "semi_sync", "async"),
                    help="sync drops deadline stragglers (the legacy "
                         "path, bitwise unchanged); semi_sync folds "
                         "within-grace stragglers into the round with a "
                         "staleness discount; async buffers them "
                         "host-side and merges them at arrival "
                         "(core/async_agg semantics)")
    ap.add_argument("--deadline-s", type=float, default=0.5,
                    help="upload deadline for the non-sync server modes")
    ap.add_argument("--grace-s", type=float, default=0.5,
                    help="semi_sync window after the deadline")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="w(tau) = (1+tau)^(-alpha) staleness discount")
    ap.add_argument("--buffer-k", type=int, default=8,
                    help="async arrival-buffer slots (earliest-due win)")
    ap.add_argument("--recovery", default="one_shot",
                    choices=("one_shot", "fec", "arq"),
                    help="uplink recovery policy, mirrored at the RATE "
                         "level: the launch routes apply the policy's "
                         "closed-form residual loss rate "
                         "(netsim/recovery.residual_loss_rate) to the "
                         "TRA channel instead of simulating packet-"
                         "level parity/retries — the engine "
                         "(cfg.recovery) owns the exact per-packet "
                         "semantics")
    ap.add_argument("--arq-retries", type=float, default=2.0,
                    help="max ARQ retransmit rounds (--recovery arq)")
    ap.add_argument("--fec-group", type=int, default=8,
                    help="FEC parity group size G (--recovery fec)")
    ap.add_argument("--sweep-loss-rates", default=None,
                    help="comma-separated TRA loss rates, e.g. "
                         "'0.0,0.1,0.3': train all scenarios at once as "
                         "one vmapped program (one compile, S replicas)")
    ap.add_argument("--debias", default="per_coord_count")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--telemetry", default="off",
                    choices=("off", "scalars", "full"),
                    help="host-side telemetry level; any non-off level "
                         "streams per-round records to --events-out "
                         "(the launch routes record the signals they "
                         "observe; absent fields mean the route does "
                         "not instrument that signal)")
    ap.add_argument("--events-out", default=None,
                    help="JSONL event-stream path (tools/flstat.py "
                         "renders it); required when --telemetry is on")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace (TensorBoard/"
                         "Perfetto) covering the training loop")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.telemetry != "off" and not args.events_out:
        ap.error("--telemetry scalars|full needs --events-out PATH")
    if args.events_out and args.telemetry == "off":
        ap.error("--events-out needs --telemetry scalars|full")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(lr=args.lr)
    if args.recovery != "one_shot":
        from repro.netsim.recovery import residual_loss_rate
        eff = float(residual_loss_rate(
            args.recovery, args.loss_rate,
            retries=args.arq_retries, group=args.fec_group))
        print(f"recovery={args.recovery}: nominal loss "
              f"{args.loss_rate:.3f} -> residual {eff:.5f}", flush=True)
        args.loss_rate = eff
        if args.sweep_loss_rates:
            rates = [float(x) for x in args.sweep_loss_rates.split(",")]
            args.sweep_loss_rates = ",".join(
                str(float(residual_loss_rate(
                    args.recovery, r, retries=args.arq_retries,
                    group=args.fec_group))) for r in rates)
    tra = TRAConfig(loss_rate=args.loss_rate, debias=args.debias)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        return _dispatch(ap, args, cfg, tcfg, tra)
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()


def _dispatch(ap, args, cfg, tcfg, tra):
    if args.server_mode != "sync":
        if args.sweep_loss_rates or args.cohort is not None:
            ap.error("--server-mode semi_sync/async is a single-scenario "
                     "full-participation route (the arrival buffer is "
                     "host-side per client)")
        if args.deadline_s <= 0:
            ap.error("--server-mode semi_sync/async needs --deadline-s > 0")
        if tra.debias == "per_coord_count":
            ap.error("--server-mode semi_sync/async needs --debias "
                     "group_rate or none (per-coord denominators cannot "
                     "ride the scalar-weight arrival buffer)")
        return _run_async(cfg, tcfg, tra, args)
    if args.sweep_loss_rates:
        if args.cohort is not None:
            ap.error("--cohort is not supported on the sweep route "
                     "(per-scenario cohorts would break the shared "
                     "batch); use the single-scenario route")
        rates = [float(x) for x in args.sweep_loss_rates.split(",")]
        return _run_sweep(cfg, tcfg, tra, args, rates)
    C = args.clients
    if args.cohort is not None and not 0 < args.cohort <= C:
        ap.error(f"--cohort must be in [1, {C}]")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    fl_step, opt = make_fl_train_step(cfg, tcfg, tra, C)
    opt_state = opt.init(params)
    fl_step = _timed(jax.jit(fl_step), "single", args)
    sufficient = jnp.asarray(
        [0.0] * args.insufficient + [1.0] * (C - args.insufficient))
    select = update = None
    if args.cohort is not None:
        select, update = _make_selector(args, C)
    rng = np.random.default_rng(0)
    writer = _open_writer(args, "single")
    try:
        for i in range(args.steps):
            batches = [synth_batch(cfg, args.batch, args.seq, rng)
                       for _ in range(C)]
            batch = {k: jnp.stack([b[k] for b in batches])
                     for k in batches[0]}
            t0 = time.time()
            participating, ids = None, None
            if select is not None:
                ids = select(i)
                mask = np.zeros(C, np.float32)
                mask[ids] = 1.0
                participating = jnp.asarray(mask)
            params, opt_state, m = fl_step(params, opt_state, batch,
                                           sufficient,
                                           jax.random.PRNGKey(1000 + i),
                                           participating=participating)
            if update is not None:
                update(ids, m)
            cohort_note = ("" if ids is None
                           else f" cohort={sorted(ids.tolist())}")
            print(f"round {i:4d} loss={float(m['loss']):8.4f} "
                  f"clients={np.asarray(m['client_losses']).round(3)}"
                  f"{cohort_note} ({time.time()-t0:.2f}s)", flush=True)
            if writer is not None:
                writer.write_round(RoundRecord(
                    round=i, train_loss=float(m["loss"]),
                    cohort=(sorted(int(x) for x in ids)
                            if ids is not None else None),
                    realized_loss=float(args.loss_rate)))
            if not np.isfinite(float(m["loss"])):
                # a NaN loss means either the model diverged or an
                # upload poisoned the aggregate — name the leaf instead
                # of a bare AssertionError so the failure is actionable
                assert_finite_tree(params, name=f"round{i}/params")
                assert_finite_tree(m, name=f"round{i}/metrics")
    finally:
        if writer is not None:
            writer.write_program_stats(tele_mod.REGISTRY.stats())
            writer.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
