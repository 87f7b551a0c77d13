"""Pallas TPU kernel: on-device Gilbert–Elliott packet-mask generation.

Each client's delivery mask is a length-P realisation of a two-state
Markov chain — a strictly sequential recurrence along the packet axis,
but embarrassingly parallel across clients. The kernel therefore works
on the transposed (P, C) layout — packets on sublanes, clients on
lanes — with grid (C // bc, P // bp), packets innermost. Each cell
walks its bp packets for bc clients in lockstep on the VPU, 8 packet
rows per aligned load and store:

    flip_p      = s ? p_bg : p_gb          (per-client, (1, bc))
    s           = u_t[p, :] < flip_p ? 1-s : s
    mask[p, :]  = u_e[p, :] >= (s ? h_b : h_g)

The chain state rides a (1, bc) VMEM scratch across the packet blocks
and is written out after the last one, which is what lets the engine
carry ``NetSimState.channel`` through its scan. Padded packets carry
u_t = 2 (never a flip), so they leave the state alone.

The counter-based per-packet uniforms (u_t, u_e) arrive as inputs —
they come from the engine's single threefry ``fold_in(base_key, t)``
invocation per round, so mask generation stays deterministic per
(seed, round) and bit-identical between the kernel and the jnp
reference (ref.py). On CPU the kernel runs in interpret mode (parity
smoke / tests); the engine's hot path uses the jnp reference there
(see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_to, resolve_interpret, round_up

ROWS = 8            # packet rows per aligned load/store (f32 sublanes)
MAX_BLOCK_P = 512   # packet rows per grid cell


def _kernel(ut_ref, ue_ref, s0_ref, pgb_ref, pbg_ref, hg_ref, hb_ref,
            m_ref, sfin_ref, s_ref):
    pgb, pbg = pgb_ref[...], pbg_ref[...]             # (1, bc)
    hg, hb = hg_ref[...], hb_ref[...]                 # (1, bc)
    bp, bc = ut_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = s0_ref[...].astype(jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, bc), 0)

    def body(i, s):
        r = pl.multiple_of(i * ROWS, ROWS)
        ut = ut_ref[pl.ds(r, ROWS), :]                # (ROWS, bc)
        ue = ue_ref[pl.ds(r, ROWS), :]
        out = jnp.zeros((ROWS, bc), jnp.float32)
        for j in range(ROWS):
            flip = jnp.where(s > 0.5, pbg, pgb)
            s = jnp.where(ut[j:j + 1] < flip, 1.0 - s, s)
            h = jnp.where(s > 0.5, hb, hg)
            delivered = (ue[j:j + 1] >= h).astype(jnp.float32)
            out = jnp.where(row == j, delivered, out)
        m_ref[pl.ds(r, ROWS), :] = out
        return s

    s = jax.lax.fori_loop(0, bp // ROWS, body, s_ref[...])
    s_ref[...] = s
    sfin_ref[...] = (s > 0.5).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def netsim_mask_call(u_t, u_e, s0, p_gb, p_bg, h_g, h_b, *,
                     block_c: int | None = None,
                     interpret: bool | None = None):
    """u_t, u_e: (C, P) uniforms; s0: (C,) int32; params: (C,) f32.
    -> (mask (C, P) f32, s_final (C,) int32). The client block is all
    of C up to 128 lanes, else 128; C and P pad to whole blocks."""
    interpret = resolve_interpret(interpret)
    C, P = u_t.shape
    bc = block_c or (C if C <= 128 else 128)
    bp = min(round_up(P, ROWS), MAX_BLOCK_P)
    Cp, Pp = round_up(C, bc), round_up(P, bp)
    tile = pl.BlockSpec((bp, bc), lambda c, p: (p, c))
    col = pl.BlockSpec((1, bc), lambda c, p: (0, c))

    def packets(u, fill):
        return jnp.pad(u.astype(jnp.float32), ((0, Cp - C), (0, Pp - P)),
                       constant_values=fill).T

    def clients(v, dt=jnp.float32):
        return pad_to(v.astype(dt), (Cp,))[None, :]

    mask, s_fin = pl.pallas_call(
        _kernel,
        grid=(Cp // bc, Pp // bp),
        in_specs=[tile, tile, col, col, col, col, col],
        out_specs=[tile, col],
        out_shape=[jax.ShapeDtypeStruct((Pp, Cp), jnp.float32),
                   jax.ShapeDtypeStruct((1, Cp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        name="netsim_mask",
        interpret=interpret,
    )(packets(u_t, 2.0), packets(u_e, 0.0), clients(s0, jnp.int32),
      clients(p_gb), clients(p_bg), clients(h_g), clients(h_b))
    return mask[:P, :C].T, s_fin[0, :C]
