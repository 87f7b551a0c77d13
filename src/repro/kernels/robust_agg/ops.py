"""Backend-dispatching wrapper for the robust uplink step.

``robust_uplink_round`` is the engine-facing entry point when the
fault model is compiled in (``FaultConfig.enabled``): one call
performs the whole DEFENDED server uplink — EF re-inject, per-packet
finite screening (quarantine bad packets *as if lost*, composing with
all four DEBIAS_MODES), per-client norm clipping, weighted or
coordinate-wise trimmed-mean aggregation, the new EF rows, masked
squared norms, and the per-client quarantine counts that feed the
reputation memory.

Structure: a jnp PREPASS computes the finite bits, screened mask /
ssq / kept fraction, the clip factor and the quarantine counts (the
per-client reductions every downstream consumer needs), then the main
pass — ``ref.robust_ref`` (pure jnp, default off-TPU) or the Pallas
kernel (`robust_agg.py`, default on TPU, ``custom_vmap``-wrapped so
sweep grids ride one batched launch) — produces the aggregate and EF
tiles. On the kernel path the defended uplink therefore reads the
(C, P, F) tensor TWICE (prepass + kernel) vs the undefended
megakernel's once — `benchmarks/faults_bench.py` reports that
overhead honestly rather than pretending defense is free.

Every defense gate is TRACED (`ScenarioCtx`): with the gates off the
expressions reduce bitwise to the undefended `uplink_fused` math —
the engine-level contract tests/test_faults.py locks against the
frozen PR-7 step. Override the impl per call or process-wide with
``REPRO_ROBUST_IMPL=kernel|ref`` (part of the engine's program cache
key, like ``REPRO_UPLINK_IMPL``).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.kernels.common import DENOM_EPS, RATE_EPS
from repro.kernels.robust_agg.ref import robust_ref
from repro.kernels.robust_agg.robust_agg import (robust_agg_batched_call,
                                                robust_agg_call)
from repro.kernels.tra_agg.ops import DEBIAS_MODES
from repro.kernels.uplink_fused.ops import debias_client_scale

ROBUST_IMPLS = ("auto", "kernel", "ref")


def resolved_impl(impl: str | None = None) -> str:
    """"kernel" or "ref" for this process/backend (same policy as the
    uplink megakernel: compiled Pallas on TPU, jnp elsewhere)."""
    impl = impl or os.environ.get("REPRO_ROBUST_IMPL", "auto")
    if impl not in ROBUST_IMPLS:
        raise ValueError(f"unknown robust impl {impl!r}")
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


class RobustUplinkOut(NamedTuple):
    agg: jnp.ndarray                 # (d_up,) defended aggregate
    ef_rows: Optional[jnp.ndarray]   # (C, d_up) new EF rows, or None
    ssq: Optional[jnp.ndarray]       # (C,) screened masked sq norms
    qcnt: jnp.ndarray                # (C,) quarantined-packet counts
    pk_ok: jnp.ndarray               # (C, P) per-packet finite bits
    s_clip: jnp.ndarray              # (C,) norm-clip factors (1 = off)
    kept: Optional[jnp.ndarray]      # (C,) screened kept fraction
    #                                  (per_client_rate mode only)


def _pack_rows(rows, P: int, F: int):
    C, d = rows.shape
    return jnp.pad(rows, ((0, 0), (0, P * F - d))).reshape(C, P, F)


@functools.lru_cache(maxsize=None)
def _kernel_dispatch(has_ef: bool, has_trim: bool, per_coord: bool,
                     trim_k: int, block_p, interpret, eps: float):
    """custom_vmap-wrapped kernel call for one static signature (cf.
    uplink_fused.ops): plain calls run the grid at S = 1; a vmapped
    call (the sweep engine) hits the scenario-batched grid."""
    kw = dict(trim_k=trim_k, block_p=block_p, interpret=interpret,
              eps=eps, per_coord=per_coord)

    names = ["x", "m", "q", "wd", "scr", "trg"]
    if has_ef:
        names.append("ef")
    if has_trim:
        names += ["g", "wpos"]

    def _split(args):
        d = dict(zip(names, args))
        return ((d["x"], d["m"], d["q"], d["wd"], d["scr"], d["trg"]),
                dict(ef=d.get("ef"), g=d.get("g"), w_pos=d.get("wpos")))

    @custom_batching.custom_vmap
    def call(*args):
        pos, opt = _split(args)
        outs = robust_agg_call(*pos, **opt, **kw)
        return tuple(o for o in outs if o is not None)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = tuple(
            a if b else jnp.broadcast_to(a, (axis_size,) + jnp.shape(a))
            for a, b in zip(args, in_batched))
        pos, opt = _split(args)
        outs = robust_agg_batched_call(*pos, **opt, **kw)
        outs = tuple(o for o in outs if o is not None)
        return outs, tuple(True for _ in outs)

    return call


def robust_uplink_round(xp, pkt_mask, weights, *, mode: str, d_up: int,
                        screen, clip_norm, trim_gate, trim_k: int = 0,
                        ef_rows=None, sufficient=None, loss_rate=None,
                        mult=None, want_ssq: bool = False,
                        block_p: int | None = None,
                        impl: str | None = None,
                        interpret: bool | None = None) -> RobustUplinkOut:
    """One defended uplink step over a packetised cohort.

    Same operand contract as ``uplink_fused.ops.uplink_round`` —
    xp (C, P, F) UNMASKED post-injection uploads, pkt_mask (C, P),
    weights (C,) (arrival-weighted; they enter the denominator) —
    plus the traced defense knobs: ``screen`` () gate, ``clip_norm``
    () threshold (``faults.CLIP_OFF`` = off), ``trim_gate`` () gate
    and the STATIC ``trim_k``. ``kept`` is computed internally from
    the SCREENED mask (quarantined packets debias like lost ones).

    The trimmed mean is an UNWEIGHTED robust location estimate of the
    per-client debiased updates: data/arrival weights only gate
    validity (weight > 0), they do not tilt the estimator — a byzantine
    client must out-vote the cohort, not out-weigh it.
    """
    assert mode in DEBIAS_MODES, mode
    C, P, F = xp.shape
    ef = ef_rows is not None
    # ---- jnp prepass: per-client reductions over the screened tensor
    x32 = xp.astype(jnp.float32)
    ef_p = _pack_rows(ef_rows, P, F).astype(jnp.float32) if ef else None
    x_eff = x32 + ef_p if ef else x32
    fin = jnp.isfinite(x_eff)
    pk_ok = fin.all(-1).astype(jnp.float32)           # (C, P)
    scr = screen > 0.5
    x_san = jnp.where(scr & ~fin, 0.0, x_eff)
    m = pkt_mask
    m_eff = jnp.where(scr, m * pk_ok, m)
    # quarantine counts: delivered-but-bad packets, regardless of the
    # screen gate (reputation observes faults even when undefended)
    qcnt = (m * (1.0 - pk_ok)).sum(-1)
    # screened masked squared norms (q-FedAvg h_k, gradient_norm
    # selection, and the clip predicate below)
    ssq = ((x_san * x_san).sum(-1) * m_eff).sum(-1)
    cn2 = clip_norm * clip_norm
    s_clip = jnp.where(
        ssq > cn2, clip_norm / jnp.sqrt(jnp.maximum(ssq, DENOM_EPS)),
        1.0)
    kept = None
    if mode == "per_client_rate":
        pad = P * F - d_up
        pcnt = jnp.full((P,), F, jnp.float32).at[-1].set(F - pad)
        kept = (m_eff @ pcnt) / d_up
    q_c = debias_client_scale(weights, mode=mode, kept=kept,
                              sufficient=sufficient,
                              loss_rate=loss_rate, mult=mult)
    q_full = q_c * s_clip
    per_coord = mode == "per_coord_count"
    w_or_den = weights if per_coord \
        else jnp.maximum(weights.sum(), DENOM_EPS)
    g = w_pos = None
    if trim_k > 0:
        # per-client estimate scale: debias without the data weights
        # (the trimmed mean is unweighted), with clip still applied
        g = debias_client_scale(jnp.ones((C,), jnp.float32), mode=mode,
                                kept=kept, sufficient=sufficient,
                                loss_rate=loss_rate, mult=mult) * s_clip
        w_pos = (weights > 0.0).astype(jnp.float32)

    # ---- main pass: aggregate + EF tiles (ref or Pallas kernel)
    if resolved_impl(impl) == "kernel":
        call = _kernel_dispatch(ef, trim_k > 0, per_coord, trim_k,
                                block_p, interpret, float(DENOM_EPS))
        args = [x32, m.astype(jnp.float32), q_full.astype(jnp.float32),
                w_or_den, jnp.asarray(screen, jnp.float32),
                jnp.asarray(trim_gate, jnp.float32)]
        if ef:
            args.append(ef_p)
        if trim_k > 0:
            args += [g, w_pos]
        outs = list(call(*args))
        agg = outs.pop(0)
        ef_out = outs.pop(0) if ef else None
    else:
        agg, ef_out, _ = robust_ref(
            x32, m, q_full, w_or_den, ef=ef_p, screen=screen,
            trim_gate=trim_gate, g=g, w_pos=w_pos, trim_k=trim_k,
            per_coord=per_coord)

    new_ef_rows = ef_out.reshape(C, P * F)[:, :d_up] \
        if ef_out is not None else None
    return RobustUplinkOut(
        agg=agg.reshape(-1)[:d_up], ef_rows=new_ef_rows,
        ssq=ssq if want_ssq else None, qcnt=qcnt, pk_ok=pk_ok,
        s_clip=s_clip, kept=kept)
