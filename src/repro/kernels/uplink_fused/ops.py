"""Backend-dispatching wrapper for the fused uplink megakernel.

``uplink_round`` is the engine-facing entry point: one call performs the
whole server uplink step — EF re-inject, delivery-mask fold, per-mode
debias scaling (all four DEBIAS_MODES), weighted aggregation with fp32
accumulation, the new EF memory rows, and (for q-FedAvg) the masked
per-client squared norms — in one pass over the (C, P, F) upload
tensor.

Implementation resolution (at call time):
  * "kernel" — the Pallas megakernel; compiled on TPU, interpret-mode
    emulation elsewhere. The default on TPU.
  * "ref"    — the pure-jnp single-pass oracle (ref.py), bit-identical
    to the pre-megakernel engine math. The default on CPU/GPU, where no
    compiled Mosaic lowering exists and interpret emulation inside the
    round scan would only add overhead over XLA's fused jnp.
Override per call (``impl=``) or process-wide with
``REPRO_UPLINK_IMPL=kernel|ref`` (tests and benchmarks force the kernel
path on CPU this way). The engine folds the resolved impl into its
compiled-program cache keys, so flipping the env var retraces.

Scenario batching: the kernel path is wrapped in
``jax.custom_batching.custom_vmap`` whose batching rule dispatches to
``uplink_fused_batched_call`` — a leading S grid axis over the SAME
kernel body. When `core/sweep.py` vmaps the round step over S
scenarios, the whole grid's uplink becomes one batched kernel launch,
bit-identical to S single-scenario calls (tests/test_uplink_fused.py).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.kernels.common import DENOM_EPS, RATE_EPS
from repro.kernels.tra_agg.ops import DEBIAS_MODES
from repro.kernels.uplink_fused.ref import uplink_ref
from repro.kernels.uplink_fused.uplink_fused import (
    uplink_fused_batched_call, uplink_fused_call)

UPLINK_IMPLS = ("auto", "kernel", "ref")


def resolved_impl(impl: str | None = None) -> str:
    """"kernel" or "ref" for this process/backend (see module doc)."""
    impl = impl or os.environ.get("REPRO_UPLINK_IMPL", "auto")
    if impl not in UPLINK_IMPLS:
        raise ValueError(f"unknown uplink impl {impl!r}")
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


def debias_client_scale(weights, *, mode, kept=None, sufficient=None,
                        loss_rate=None, mult=None):
    """Fold the per-mode debias estimator into per-client scales q_c.

    The expressions (and their guard epsilons) are the single source of
    truth for the mode semantics shared by the megakernel, the jnp
    reference and ``engine.fused_debias_aggregate``; `kernels/tra_agg`
    mirrors them on pre-masked inputs.
    """
    q_c = weights if mult is None else weights * mult
    if mode == "per_client_rate":
        q_c = q_c / jnp.maximum(kept, RATE_EPS)
    elif mode == "group_rate":
        q_c = q_c * jnp.where(
            sufficient.astype(bool), 1.0,
            1.0 / jnp.maximum(1.0 - loss_rate, RATE_EPS))
    return q_c


@functools.lru_cache(maxsize=None)
def _kernel_dispatch(has_ef: bool, per_coord: bool, want_ssq: bool,
                     block_p, block_c, interpret, eps: float):
    """custom_vmap-wrapped kernel call for one static signature: plain
    calls run the grid at S = 1; a vmapped call (the sweep engine)
    dispatches to the scenario-batched grid."""
    kw = dict(want_ssq=want_ssq, per_coord=per_coord, block_p=block_p,
              block_c=block_c, interpret=interpret, eps=eps)

    def _present(outs):
        return tuple(o for o in outs if o is not None)

    if has_ef:
        @custom_batching.custom_vmap
        def call(x, m, q, wd, ef):
            return _present(uplink_fused_call(x, m, q, wd, ef=ef, **kw))

        @call.def_vmap
        def _rule(axis_size, in_batched, x, m, q, wd, ef):
            x, m, q, wd, ef = _broadcast(axis_size, in_batched,
                                         (x, m, q, wd, ef))
            outs = _present(
                uplink_fused_batched_call(x, m, q, wd, ef=ef, **kw))
            return outs, tuple(True for _ in outs)
    else:
        @custom_batching.custom_vmap
        def call(x, m, q, wd):
            return _present(uplink_fused_call(x, m, q, wd, **kw))

        @call.def_vmap
        def _rule(axis_size, in_batched, x, m, q, wd):
            x, m, q, wd = _broadcast(axis_size, in_batched, (x, m, q, wd))
            outs = _present(uplink_fused_batched_call(x, m, q, wd, **kw))
            return outs, tuple(True for _ in outs)

    return call


def _broadcast(axis_size, in_batched, args):
    """Give every operand the leading scenario axis the batched grid
    expects (unbatched operands are broadcast — rare in practice: every
    sweep input derives from per-scenario state)."""
    return tuple(
        a if b else jnp.broadcast_to(a, (axis_size,) + jnp.shape(a))
        for a, b in zip(args, in_batched))


def _pack_rows(rows, P: int, F: int):
    """(C, d) rows -> zero-padded (C, P, F) packet view."""
    C, d = rows.shape
    return jnp.pad(rows, ((0, 0), (0, P * F - d))).reshape(C, P, F)


def uplink_round(xp, pkt_mask, weights, *, mode: str, d_up: int,
                 ef_rows=None, kept=None, sufficient=None, loss_rate=None,
                 mult=None, want_ssq: bool = False,
                 block_p: int | None = None, block_c: int | None = None,
                 impl: str | None = None, interpret: bool | None = None,
                 stream_dtype=None):
    """One fused uplink step over a packetised cohort.

    xp: (C, P, F) UNMASKED uploads WITHOUT error feedback;
    pkt_mask: (C, P); weights: (C,) aggregation weights (enter the
    denominator); ef_rows: (C, d_up) EF memory rows or None; kept (C,) /
    sufficient (C,) / loss_rate () feed the per-mode scales exactly as
    in ``debias_client_scale``; ``mult`` scales clients on top of
    ``weights`` without entering the denominator (q-FedAvg F^q).

    Returns ``(agg (d_up,), new_ef_rows (C, d_up) | None,
    ssq (C,) | None)`` where ssq are the masked squared norms of the
    EF-adjusted uploads. ``stream_dtype`` (e.g. bf16) engages the
    kernel's bf16-stream/fp32-accumulate mode; leave None for the
    bit-exact f32 default.
    """
    assert mode in DEBIAS_MODES, mode
    C, P, F = xp.shape
    q_c = debias_client_scale(weights, mode=mode, kept=kept,
                              sufficient=sufficient, loss_rate=loss_rate,
                              mult=mult)
    per_coord = mode == "per_coord_count"
    w_or_den = weights if per_coord \
        else jnp.maximum(weights.sum(), DENOM_EPS)
    ef_p = _pack_rows(ef_rows, P, F) if ef_rows is not None else None

    if resolved_impl(impl) == "kernel":
        x = xp if stream_dtype is None else xp.astype(stream_dtype)
        ef_k = ef_p if ef_p is None or stream_dtype is None \
            else ef_p.astype(stream_dtype)
        call = _kernel_dispatch(ef_k is not None, per_coord, want_ssq,
                                block_p, block_c, interpret,
                                float(DENOM_EPS))
        args = (x, pkt_mask.astype(jnp.float32), q_c.astype(jnp.float32),
                w_or_den)
        outs = list(call(*args, ef_k) if ef_k is not None
                    else call(*args))
        agg = outs.pop(0)
        ef_out = outs.pop(0) if ef_k is not None else None
        ssq = outs.pop(0) if want_ssq else None
    else:
        # ref path honours the stream contract too: inputs rounded to
        # the stream dtype (uplink_ref upcasts to f32 to accumulate),
        # EF rows written back in it — same dtypes on every backend.
        x = xp if stream_dtype is None else xp.astype(stream_dtype)
        ef_r = ef_p if ef_p is None or stream_dtype is None \
            else ef_p.astype(stream_dtype)
        agg, ef_out, ssq = uplink_ref(x, pkt_mask, q_c, w_or_den,
                                      ef=ef_r, want_ssq=want_ssq,
                                      per_coord=per_coord)
        if ef_out is not None and stream_dtype is not None:
            ef_out = ef_out.astype(stream_dtype)

    new_ef_rows = ef_out.reshape(C, P * F)[:, :d_up] \
        if ef_out is not None else None
    return agg.reshape(-1)[:d_up], new_ef_rows, ssq


def uplink_round_scenarios(xp, pkt_mask, weights, *, mode: str, d_up: int,
                           ef_rows=None, kept=None, sufficient=None,
                           loss_rate=None, mult=None, want_ssq=False,
                           **kw):
    """Scenario-batched (S, C, P, F) convenience entry: vmaps
    ``uplink_round`` over the leading axis of every provided operand —
    on the kernel path this lands in ``uplink_fused_batched_call`` via
    the custom_vmap rule (one launch for all S scenarios)."""
    optional = dict(ef_rows=ef_rows, kept=kept, sufficient=sufficient,
                    loss_rate=loss_rate, mult=mult)
    names = [k for k, v in optional.items() if v is not None]

    def one(xp, pkt_mask, weights, *opts):
        return uplink_round(xp, pkt_mask, weights, mode=mode, d_up=d_up,
                            want_ssq=want_ssq,
                            **dict(zip(names, opts)), **kw)

    return jax.vmap(one)(xp, pkt_mask, weights,
                         *[optional[k] for k in names])
