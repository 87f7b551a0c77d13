"""Pallas TPU megakernel: the whole TRA uplink step in ONE pass over the
packetised upload tensor.

For a cohort of C clients whose uploads are viewed as (C, P, F) packets
(F = 256 f32 coords = one 1 KiB UDP payload), with per-packet delivery
masks m (C, P), per-client debias scales q (C,) (all four DEBIAS_MODES
pre-folded by ops.py) and raw aggregation weights w (C,), the kernel
computes — in a single read of x (and of the error-feedback memory ef):

    x_eff[c, p, f] = x[c, p, f] + ef[c, p, f]            (EF re-inject)
    agg[p, f]      = sum_c q[c] m[c, p] x_eff[c, p, f]
                     / den[p]                             (debias-agg)
    ef_out[c,p,f]  = x_eff[c, p, f] * (1 - m[c, p])       (EF update)
    ssq[c]         = sum_p m[c, p] sum_f x_eff[c, p, f]^2 (q-FedAvg h_k)

where den is either the per-coordinate masked weight sum
``sum_c w[c] m[c, p]`` (``per_coord_count``, accumulated in the same
pass) or a precomputed scalar ``max(sum_c w[c], DENOM_EPS)`` (all other
modes). The unfused chain (EF add, mask multiply, einsum aggregate, EF
scatter source) reads the (C, P, F) tensor >= 3 times and writes the
EF-adjusted intermediate once; this kernel reads x and ef once each and
writes only the true outputs.

Tiling: grid (S, P//bp, C//bc) — C innermost, so on TPU (sequential
grid) the (bp, F) fp32 aggregate accumulator and the (bp,) denominator
live in VMEM scratch across the client loop while the output tile's
block index stays fixed; the aggregate is divided and written once on
the last client step. EF tiles stream through: each grid cell reads a
(bc, bp, F) tile of x/ef and writes the matching ef_out tile. The mask
enters transposed, (P, C), and the squared norms leave per packet in
the same layout, so their blocks are legal at any packet block of 8
rows (see ``pick_blocks``).

bf16-stream / fp32-accumulate contract: x and ef may arrive as bf16
(halving HBM traffic); every tile is upcast to fp32 on load, the
aggregate and ssq accumulate in fp32, and ef_out is written back in the
stream dtype. The f32 default is bit-exact against ref.py (locked by
tests/test_uplink_fused.py).

``uplink_fused_batched_call`` is the kernel: a leading S grid axis over
(S, C, P, F) inputs, so `core/sweep.py` grids ride the SAME kernel —
ops.py wires it in as the jax.vmap rule of the single-scenario call
(`jax.custom_batching.custom_vmap`), which is the batched call at S = 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (DENOM_EPS, pad_to, resolve_interpret,
                                  round_up, tile_budget)

# Per-backend budget for one (bc, bp, F) tile of x (bytes); None = the
# whole (C, P) in one block. Mosaic's tiling rule shapes every block
# here: the last two dims of a block are multiples of (8, 128) or span
# the array. Blocks are x/ef (bc, bp, F), the transposed mask and the
# per-packet ssq (bp, bc), the scales (bc, 1) and agg (bp, F), so
# bp is P or a multiple of 8 and bc is C or a multiple of 128; the
# wrapper zero-pads C and P up to whole blocks. On TPU ~1 MiB f32 tiles
# keep x, ef and ef_out double-buffered well inside the scoped VMEM. On
# CPU the kernel only runs in interpret mode (tests), where one block
# is fastest and reduces in the reference's order.
_TILE_BYTES = {"tpu": 1 << 20, "cpu": None}


def pick_blocks(C: int, P: int, F: int = 256, itemsize: int = 4,
                block_p: int | None = None, block_c: int | None = None):
    """(block_p, block_c) for a (C, P, F) upload tensor: the whole array
    when it fits the backend's tile budget, else full-cohort (or
    128-client) blocks of as many 8-packet rows as fit. Explicit
    arguments override the choice; the call pads to whole blocks."""
    budget = tile_budget(_TILE_BYTES, "uplink_fused")
    if block_c is None:
        block_c = C if budget is None or C <= 128 else 128
    if block_p is None:
        row = block_c * F * itemsize
        block_p = P if budget is None or P * row <= budget \
            else max(8, budget // row // 8 * 8)
    return block_p, block_c


def _body(x, ef, m, q, wden, den, agg_at, efo_at, ssq_at,
          acc_ref, den_acc_ref, ci, *, nc, per_coord, eps, out_dtype):
    """One grid cell. ``m`` arrives as the (bp, bc) tile of the
    transposed mask (lane-dense over clients) and is turned back here."""
    x = x.astype(jnp.float32)
    if ef is not None:
        x = x + ef.astype(jnp.float32)            # EF re-inject, fp32
    m = m.T                                       # (bc, bp)
    wm = m * q                                    # (bc, bp)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_acc_ref[...] = jnp.zeros_like(den_acc_ref)

    acc_ref[...] += jnp.einsum("cpf,cp->pf", x, wm)
    if per_coord:
        den_acc_ref[...] += jnp.sum(m * wden, axis=0)
    if efo_at is not None:
        efo_at[...] = (x * (1.0 - m[..., None])).astype(out_dtype)
    if ssq_at is not None:
        ssq_at[...] = ((x * x).sum(-1) * m).T     # per packet, (bp, bc)

    @pl.when(ci == nc - 1)
    def _finish():
        if per_coord:
            d = jnp.maximum(den_acc_ref[...], eps)[:, None]
        else:
            d = den                               # pre-guarded scalar
        agg_at[...] = acc_ref[...] / d


def _kernel(*refs, nc, per_coord, has_ef, want_ssq, eps, out_dtype):
    """Split the flat pallas ref list back into named operands."""
    it = iter(refs)
    x = next(it)
    ef = next(it) if has_ef else None
    m, q = next(it), next(it)
    wden = next(it) if per_coord else None
    den = None if per_coord else next(it)
    agg = next(it)
    efo = next(it) if has_ef else None
    ssq = next(it) if want_ssq else None
    acc, den_acc = next(it), next(it)
    _body(x[0], ef[0] if ef is not None else None, m[0], q[0],
          wden[0] if wden is not None else None,
          den[0, 0, 0] if den is not None else None,
          agg.at[0], efo.at[0] if efo is not None else None,
          ssq.at[0] if ssq is not None else None,
          acc, den_acc, pl.program_id(2),
          nc=nc, per_coord=per_coord, eps=eps, out_dtype=out_dtype)


def uplink_fused_batched_call(x, m, q, w_or_den, *, ef=None,
                              want_ssq=False, block_p: int | None = None,
                              block_c: int | None = None,
                              interpret: bool | None = None,
                              eps: float = DENOM_EPS, per_coord: bool):
    """Scenario-batched megakernel over (S, C, P, F) inputs: grid
    (S, P//bp, C//bc). Shapes follow ``uplink_fused_call`` with a
    leading S on every operand (``w_or_den`` is (S, C) when
    ``per_coord``, else (S,) ready scalars)."""
    S, C, P, F = x.shape
    bp, bc = pick_blocks(C, P, F, x.dtype.itemsize, block_p, block_c)
    Cp, Pp = round_up(C, bc), round_up(P, bp)
    gp, nc = Pp // bp, Cp // bc
    interpret = resolve_interpret(interpret)
    has_ef = ef is not None
    # padded clients carry zero uploads, mask and weights; padded
    # packets are undelivered zeros: both add exact zeros and are
    # sliced off below
    tile = pl.BlockSpec((1, bc, bp, F), lambda s, p, c: (s, c, p, 0))
    col = pl.BlockSpec((1, bc, 1), lambda s, p, c: (s, c, 0))
    pkt = pl.BlockSpec((1, bp, bc), lambda s, p, c: (s, p, c))

    in_specs = [tile]
    operands = [pad_to(x, (S, Cp, Pp, F))]
    if has_ef:
        in_specs.append(tile)
        operands.append(pad_to(ef.astype(x.dtype), (S, Cp, Pp, F)))
    in_specs += [pkt, col]
    operands += [pad_to(m.astype(jnp.float32), (S, Cp, Pp)).swapaxes(1, 2),
                 pad_to(q.astype(jnp.float32), (S, Cp))[..., None]]
    if per_coord:
        in_specs.append(col)
        operands.append(
            pad_to(w_or_den.astype(jnp.float32), (S, Cp))[..., None])
    else:
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda s, p, c: (s, 0, 0)))
        operands.append(
            jnp.asarray(w_or_den, jnp.float32).reshape(S, 1, 1))

    out_specs = [pl.BlockSpec((1, bp, F), lambda s, p, c: (s, p, 0))]
    out_shape = [jax.ShapeDtypeStruct((S, Pp, F), jnp.float32)]
    if has_ef:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((S, Cp, Pp, F), x.dtype))
    if want_ssq:
        out_specs.append(pkt)
        out_shape.append(jax.ShapeDtypeStruct((S, Pp, Cp), jnp.float32))

    outs = list(pl.pallas_call(
        functools.partial(_kernel, nc=nc, per_coord=per_coord,
                          has_ef=has_ef, want_ssq=want_ssq, eps=eps,
                          out_dtype=x.dtype),
        grid=(S, gp, nc),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bp, F), jnp.float32),   # agg accum
                        pltpu.VMEM((bp,), jnp.float32)],    # den accum
        name="uplink_fused",
        interpret=interpret,
    )(*operands))
    agg = outs.pop(0)[:, :P]
    ef_out = outs.pop(0)[:, :C, :P] if has_ef else None
    ssq = outs.pop(0)[:, :P, :C].swapaxes(1, 2).sum(-1) if want_ssq \
        else None
    return agg, ef_out, ssq


def uplink_fused_call(x, m, q, w_or_den, *, ef=None, want_ssq=False,
                      block_p: int | None = None, block_c: int | None = None,
                      interpret: bool | None = None, eps: float = DENOM_EPS,
                      per_coord: bool):
    """Single-scenario megakernel call: the batched grid with S = 1.

    x: (C, P, F) packetised UNMASKED uploads, f32 or bf16 (the stream
    dtype); ef: matching (C, P, F) error-feedback tile or None;
    m: (C, P) f32 delivery mask; q: (C,) f32 pre-folded debias scales.
    ``w_or_den``: per-client raw weights (C,) when ``per_coord`` (the
    kernel accumulates the per-coordinate denominator itself), else the
    READY scalar denominator () — already ``max(sum w, DENOM_EPS)``.

    Returns (agg (P, F) f32, ef_out (C, P, F) stream-dtype | None,
    ssq (C,) f32 masked squared norms | None).
    """
    outs = uplink_fused_batched_call(
        x[None], m[None], q[None], jnp.asarray(w_or_den)[None],
        ef=None if ef is None else ef[None], want_ssq=want_ssq,
        block_p=block_p, block_c=block_c, interpret=interpret, eps=eps,
        per_coord=per_coord)
    return tuple(None if o is None else o[0] for o in outs)
