"""Pallas TPU kernel: screen + clip + trimmed-mean robust aggregation
in one pass over the packetised upload tensor.

For a cohort of C clients viewed as (C, P, F) packets with delivery
masks m (C, P), per-client scales q (C,) (all four DEBIAS_MODES plus
the clip factor pre-folded by ops.py) and traced defense gates, each
grid cell computes — in a single read of x (and ef):

    x_eff   = x + ef                                  (EF re-inject)
    ok[c,p] = all_f isfinite(x_eff[c, p, :])          (finite screen)
    x_san   = where(screen & ~isfinite, 0, x_eff)     (sanitise)
    m_eff   = where(screen, m * ok, m)                (quarantine)
    agg     = sum_c q[c] m_eff[c, p] x_san[c, p, f] / den[p]
    agg     = where(trim, trimmed_mean_c(g[c] x_san), agg)
    ef_out  = x_san * (1 - m)          (lost packets only — quarantined
                                        payloads are never recycled)

Tiling: the trimmed mean is a cross-CLIENT order statistic, so the
client axis is NOT tiled — grid (S, P//bp) with (C, bp, F) blocks (the
whole cohort of one packet stripe in VMEM; ``pick_blocks_r`` sizes bp
to keep the resident x+ef tiles under the VMEM budget). That removes
the scratch accumulators the uplink megakernel needs: every output
tile completes in its own grid cell.

The trim extraction is k passes of masked min/max with
first-occurrence removal via a min over client indices (Mosaic has no
``cumsum``, ``jnp.sort`` or ``argmin`` lowering), deliberately a
different algorithm from the ``jnp.sort`` reference oracle in ref.py.

``robust_agg_batched_call`` is the kernel, with a leading S grid axis
over (S, C, P, F) inputs; ops.py wires it in as the ``custom_vmap``
rule of the single call (the batched call at S = 1), so a sweep grid's
defended uplink is one batched launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (DENOM_EPS, pad_to, resolve_interpret,
                                  round_up, tile_budget)
from repro.kernels.robust_agg.ref import TRIM_BIG

# Per-backend budget (bytes) for one resident (C, bp, F) f32 tile;
# None = the whole (C, P) in one block (CPU runs the kernel only in
# interpret mode, for tests). The kernel holds ~17 such tiles in VMEM
# (x, ef and ef_out double-buffered plus the body's temporaries: at
# C = 64 with 1 MiB tiles the v5e compiler asks for 17.3 MiB of its
# 16 MiB scoped limit), so TPU tiles stay at 0.5 MiB. The packet block
# is P or a multiple of 8 rows: the mask enters transposed, (P, C), so
# its (bp, C) block is legal under Mosaic's (8, 128) tiling rule.
_TILE_BYTES = {"tpu": 1 << 19, "cpu": None}


def pick_blocks_r(C: int, P: int, F: int,
                  block_p: int | None = None) -> int:
    """Packet block bp for the client-resident layout: all of P when a
    (C, P, F) f32 tile fits the backend's budget, else the most 8-packet
    rows that do. The call pads P up to whole blocks."""
    budget = tile_budget(_TILE_BYTES, "robust_agg")
    if block_p is not None:
        return block_p
    row = 4 * C * F
    return P if budget is None or P * row <= budget \
        else max(8, budget // row // 8 * 8)


def _trimmed_extract(y, valid, k: int):
    """k-pass min/max trimmed mean over axis 0 (clients).

    y: (C, bp, F); valid: (C, bp, 1) f32. Per coordinate: remove the k
    smallest and k largest valid values by repeated masked min/max
    (the first client holding the extreme retires — a min over client
    indices — so duplicates retire one per pass), then average the
    remainder; <= 2k valid values falls back to the plain masked mean.
    """
    C = y.shape[0]
    vb = valid > 0.0
    n = valid.sum(0)                                     # (bp, 1)
    total = (y * valid).sum(0)                           # (bp, F)
    y_lo = jnp.where(vb, y, TRIM_BIG)
    y_hi = jnp.where(vb, y, -TRIM_BIG)
    cid = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    bot = jnp.zeros_like(total)
    top = jnp.zeros_like(total)

    def retire(vals, cur, fill):
        first = jnp.where(vals == cur[None], cid, C).min(axis=0)
        return jnp.where(cid == first[None], fill, vals)

    for _ in range(k):
        cur = y_lo.min(axis=0)
        y_lo = retire(y_lo, cur, TRIM_BIG)
        bot = bot + cur
        cur = y_hi.max(axis=0)
        y_hi = retire(y_hi, cur, -TRIM_BIG)
        top = top + cur
    cnt = jnp.maximum(n - 2.0 * k, 1.0)
    return jnp.where(n > 2.0 * k, (total - top - bot) / cnt,
                     total / jnp.maximum(n, 1.0))


def _body(x, ef, m, q, g, wpos, wden, den, scr, trg, agg_at, efo_at, *,
          per_coord, trim_k, eps, out_dtype):
    """One grid cell (whole cohort x one packet stripe). ``m`` arrives
    as the (bp, C) tile of the transposed mask and is turned back here."""
    x = x.astype(jnp.float32)
    if ef is not None:
        x = x + ef.astype(jnp.float32)                # EF re-inject
    m = m.T                                           # (C, bp)
    fin = jnp.isfinite(x)
    scr_on = scr > 0.5
    x = jnp.where(scr_on & ~fin, 0.0, x)              # sanitise
    ok = fin.all(-1).astype(jnp.float32)              # (C, bp)
    m_eff = jnp.where(scr_on, m * ok, m)              # quarantine
    num = jnp.einsum("cpf,cp->pf", x, m_eff * q)
    if per_coord:
        d = jnp.maximum((m_eff * wden).sum(axis=0), eps)[:, None]
    else:
        d = den                                       # ready scalar
    agg = num / d
    if trim_k > 0:
        y = x * g[..., None]                          # g: (C, 1)
        agg_t = _trimmed_extract(y, (m_eff * wpos)[..., None], trim_k)
        agg = jnp.where(trg > 0.5, agg_t, agg)
    agg_at[...] = agg
    if efo_at is not None:
        efo_at[...] = (x * (1.0 - m[..., None])).astype(out_dtype)


def _kernel(*refs, per_coord, has_ef, has_trim, trim_k, eps, out_dtype):
    """Split the flat pallas ref list back into named operands."""
    it = iter(refs)
    x = next(it)
    ef = next(it) if has_ef else None
    m, q = next(it), next(it)
    g = next(it) if has_trim else None
    wpos = next(it) if has_trim else None
    wden = next(it) if per_coord else None
    den = None if per_coord else next(it)
    scr, trg = next(it), next(it)
    agg = next(it)
    efo = next(it) if has_ef else None
    _body(x[0], ef[0] if ef is not None else None, m[0], q[0],
          g[0] if g is not None else None,
          wpos[0] if wpos is not None else None,
          wden[0] if wden is not None else None,
          den[0, 0, 0] if den is not None else None,
          scr[0, 0, 0], trg[0, 0, 0],
          agg.at[0], efo.at[0] if efo is not None else None,
          per_coord=per_coord, trim_k=trim_k, eps=eps,
          out_dtype=out_dtype)


def robust_agg_batched_call(x, m, q, w_or_den, screen, trim_gate, *,
                            ef=None, g=None, w_pos=None, trim_k: int = 0,
                            block_p: int | None = None,
                            interpret: bool | None = None,
                            eps: float = DENOM_EPS, per_coord: bool):
    """Scenario-batched kernel: leading S axis on every operand
    ((S,) gates, (S,) or (S, C) ``w_or_den``), grid (S, P//bp)."""
    S, C, P, F = x.shape
    bp = pick_blocks_r(C, P, F, block_p)
    Pp = round_up(P, bp)
    interpret = resolve_interpret(interpret)
    has_ef = ef is not None
    has_trim = trim_k > 0
    # padded packets are undelivered zeros, sliced off below
    tile = pl.BlockSpec((1, C, bp, F), lambda s, p: (s, 0, p, 0))
    col = pl.BlockSpec((1, C, 1), lambda s, p: (s, 0, 0))
    one = pl.BlockSpec((1, 1, 1), lambda s, p: (s, 0, 0))

    def cols(v):
        return v.astype(jnp.float32)[..., None]

    def scalars(v):
        return jnp.asarray(v, jnp.float32).reshape(S, 1, 1)

    in_specs = [tile]
    operands = [pad_to(x, (S, C, Pp, F))]
    if has_ef:
        in_specs.append(tile)
        operands.append(pad_to(ef.astype(x.dtype), (S, C, Pp, F)))
    in_specs += [pl.BlockSpec((1, bp, C), lambda s, p: (s, p, 0)), col]
    operands += [pad_to(m.astype(jnp.float32), (S, C, Pp)).swapaxes(1, 2),
                 cols(q)]
    if has_trim:
        in_specs += [col, col]
        operands += [cols(g), cols(w_pos)]
    in_specs += [col if per_coord else one, one, one]
    operands += [cols(w_or_den) if per_coord else scalars(w_or_den),
                 scalars(screen), scalars(trim_gate)]

    out_specs = [pl.BlockSpec((1, bp, F), lambda s, p: (s, p, 0))]
    out_shape = [jax.ShapeDtypeStruct((S, Pp, F), jnp.float32)]
    if has_ef:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((S, C, Pp, F), x.dtype))

    outs = list(pl.pallas_call(
        functools.partial(_kernel, per_coord=per_coord, has_ef=has_ef,
                          has_trim=has_trim, trim_k=trim_k, eps=eps,
                          out_dtype=x.dtype),
        grid=(S, Pp // bp),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="robust_agg",
        interpret=interpret,
    )(*operands))
    agg = outs.pop(0)[:, :P]
    ef_out = outs.pop(0)[:, :, :P] if has_ef else None
    return agg, ef_out


def robust_agg_call(x, m, q, w_or_den, screen, trim_gate, *, ef=None,
                    g=None, w_pos=None, trim_k: int = 0,
                    block_p: int | None = None,
                    interpret: bool | None = None,
                    eps: float = DENOM_EPS, per_coord: bool):
    """Single-scenario call: the batched grid with S = 1.

    Operand contract mirrors ``uplink_fused_call`` (x/ef (C, P, F),
    m (C, P), q (C,), ``w_or_den`` per-coord weights or ready scalar)
    plus the traced gates: ``screen`` / ``trim_gate`` () f32, and —
    when ``trim_k > 0`` — ``g`` (C,) trim estimate scales and
    ``w_pos`` (C,) weight>0 validity.

    Returns (agg (P, F) f32, ef_out (C, P, F) stream-dtype | None).
    """
    def lead(v):
        return None if v is None else jnp.asarray(v)[None]

    outs = robust_agg_batched_call(
        x[None], m[None], q[None], lead(w_or_den), lead(screen),
        lead(trim_gate), ef=lead(ef), g=lead(g), w_pos=lead(w_pos),
        trim_k=trim_k, block_p=block_p, interpret=interpret, eps=eps,
        per_coord=per_coord)
    return tuple(None if o is None else o[0] for o in outs)
