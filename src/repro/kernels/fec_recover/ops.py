"""Backend-dispatching wrapper for on-device FEC mask repair.

``fec_recover`` is the engine-facing entry point. Implementation
resolution mirrors `kernels/netsim_mask/ops.py`:

  * "kernel" — the Pallas group-repair kernel; compiled on TPU,
    interpret-mode emulation elsewhere. The default on TPU.
  * "ref"    — the pure-jnp reshape/reduce oracle (ref.py),
    bit-identical to the kernel. The default on CPU/GPU.

Override per call (``impl=``) or process-wide with
``REPRO_FEC_IMPL=kernel|ref``; the engine folds the resolved impl into
its compiled-program cache keys. Under ``jax.vmap`` (the sweep
engine's scenario axis) the kernel path batches through pallas_call's
standard vmap rule.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.fec_recover.fec_recover import fec_recover_call
from repro.kernels.fec_recover.ref import fec_recover_ref

FEC_IMPLS = ("auto", "kernel", "ref")


def resolved_impl(impl: str | None = None) -> str:
    """"kernel" or "ref" for this process/backend (see module doc)."""
    impl = impl or os.environ.get("REPRO_FEC_IMPL", "auto")
    if impl not in FEC_IMPLS:
        raise ValueError(f"unknown fec impl {impl!r}")
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


def fec_recover(mask, parity, *, group: int, impl: str | None = None,
                block_c: int | None = None,
                interpret: bool | None = None):
    """Group-parity mask repair for a cohort.

    mask: (C, P) f32 delivery mask (1 = delivered); parity: (C, Gn)
    f32 parity delivery mask with Gn = ceil(P / group). Returns the
    repaired (C, P) f32 mask — a group with exactly one data loss and
    a delivered parity has that loss flipped back to delivered.
    """
    P = mask.shape[1]
    if resolved_impl(impl) == "kernel":
        gn = parity.shape[1]
        pad = gn * group - P
        mp = jnp.pad(mask, ((0, 0), (0, pad)), constant_values=1.0)
        out = fec_recover_call(mp, parity, group=group, block_c=block_c,
                               interpret=interpret)
        return out[:, :P]
    return fec_recover_ref(mask, parity, group)
