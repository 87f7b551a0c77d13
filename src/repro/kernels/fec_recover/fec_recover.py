"""Pallas TPU kernel: FEC group-parity repair of delivery masks.

The FEC recovery policy (netsim/recovery.py) attaches one XOR parity
packet to every group of G data packets; a group that lost EXACTLY one
data packet and whose parity arrived is repaired on device before the
uplink megakernel ever sees the mask. The repair itself is a pure
per-group reduction — embarrassingly parallel across clients AND
groups — so the kernel needs no loop: the wrapper lays the mask out as
(Gn, G, C) (groups leading, the G packets of a group on sublanes,
clients on lanes) and the parity as (Gn, C), and every grid cell of
(C // bc, Gn // bg) repairs a (bg, G, bc) block in one vector pass:

    n_lost[g, c] = sum_j (1 - mask[g, j, c])
    repair[g, c] = (n_lost == 1) & (parity[g, c] > 0.5)
    mask[g, :, c] |= repair[g, c]                 (only 0 -> 1 flips)

Callers pre-pad P to a multiple of G with delivered packets (ops.py).
Exact 0/1 comparisons only — bit-identical to the jnp reference
(ref.py) on every backend, which the parity smoke asserts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import pad_to, resolve_interpret, round_up

MAX_BLOCK_G = 256   # parity groups per grid cell


def _kernel(m_ref, par_ref, out_ref):
    mask = m_ref[...]                                 # (bg, G, bc)
    n_lost = (1.0 - mask).sum(axis=1)                 # (bg, bc)
    repair = (n_lost == 1.0) & (par_ref[...] > 0.5)   # (bg, bc)
    out_ref[...] = jnp.where(repair[:, None, :] & (mask < 0.5), 1.0, mask)


@functools.partial(jax.jit,
                   static_argnames=("group", "block_c", "interpret"))
def fec_recover_call(mask, parity, *, group: int,
                     block_c: int | None = None,
                     interpret: bool | None = None):
    """mask: (C, P_pad) f32 with P_pad % group == 0 (pre-padded with
    delivered packets); parity: (C, Gn) f32, Gn = P_pad // group.
    -> repaired (C, P_pad) f32 mask. The client block is all of C up
    to 128 lanes, else 128; C and Gn pad to whole blocks (padded groups
    are delivered and have no parity, so nothing changes there)."""
    interpret = resolve_interpret(interpret)
    C, p_pad = mask.shape
    gn = parity.shape[1]
    assert p_pad == gn * group, (p_pad, gn, group)
    bc = block_c or (C if C <= 128 else 128)
    bg = gn if gn <= MAX_BLOCK_G else MAX_BLOCK_G
    Cp, Gp = round_up(C, bc), round_up(gn, bg)
    m = jnp.pad(mask.astype(jnp.float32), ((0, Cp - C), (0, 0)),
                constant_values=1.0).T.reshape(gn, group, Cp)
    m = jnp.pad(m, ((0, Gp - gn), (0, 0), (0, 0)), constant_values=1.0)
    par = pad_to(parity.astype(jnp.float32).T, (Gp, Cp))
    mtile = pl.BlockSpec((bg, group, bc), lambda c, g: (g, 0, c))
    out = pl.pallas_call(
        _kernel,
        grid=(Cp // bc, Gp // bg),
        in_specs=[mtile, pl.BlockSpec((bg, bc), lambda c, g: (g, c))],
        out_specs=mtile,
        out_shape=jax.ShapeDtypeStruct((Gp, group, Cp), jnp.float32),
        name="fec_recover",
        interpret=interpret,
    )(m, par)
    return out[:gn].reshape(p_pad, Cp)[:, :C].T
