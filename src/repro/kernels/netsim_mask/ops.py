"""Backend-dispatching wrapper for on-device Gilbert–Elliott masks.

``ge_packet_mask`` is the engine-facing entry point. Implementation
resolution mirrors `kernels/uplink_fused/ops.py`:

  * "kernel" — the Pallas recurrence kernel; compiled on TPU,
    interpret-mode emulation elsewhere. The default on TPU.
  * "ref"    — the pure-jnp ``lax.scan`` oracle (ref.py), bit-identical
    to the kernel. The default on CPU/GPU, where the sequential
    recurrence has no compiled Pallas lowering and XLA's fused scan is
    the fast path.

Override per call (``impl=``) or process-wide with
``REPRO_NETSIM_IMPL=kernel|ref``; the engine folds the resolved impl
into its compiled-program cache keys. Under ``jax.vmap`` (the sweep
engine's scenario axis) the kernel path batches through pallas_call's
standard vmap rule — a leading scenario grid axis over the same body.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.netsim_mask.netsim_mask import netsim_mask_call
from repro.kernels.netsim_mask.ref import ge_mask_ref

NETSIM_IMPLS = ("auto", "kernel", "ref")


def resolved_impl(impl: str | None = None) -> str:
    """"kernel" or "ref" for this process/backend (see module doc)."""
    impl = impl or os.environ.get("REPRO_NETSIM_IMPL", "auto")
    if impl not in NETSIM_IMPLS:
        raise ValueError(f"unknown netsim impl {impl!r}")
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return impl


def ge_packet_mask(u_t, u_e, s0, p_gb, p_bg, h_g, h_b, *,
                   impl: str | None = None, block_c: int | None = None,
                   interpret: bool | None = None):
    """Gilbert–Elliott delivery masks for a cohort.

    u_t, u_e: (C, P) per-packet uniforms (transition / emission);
    s0: (C,) int32 channel states; p_gb, p_bg, h_g, h_b: scalars or
    (C,) per-client probabilities (broadcast here, so per-scenario
    scalars and per-client rates take the same path).

    Returns (mask (C, P) f32 with 1 = delivered, s_final (C,) int32).
    """
    C, P = u_t.shape

    def _c(v):
        return jnp.broadcast_to(jnp.asarray(v, jnp.float32), (C,))

    p_gb, p_bg, h_g, h_b = _c(p_gb), _c(p_bg), _c(h_g), _c(h_b)
    s0 = s0.astype(jnp.int32)
    if resolved_impl(impl) == "kernel":
        return netsim_mask_call(u_t, u_e, s0, p_gb, p_bg, h_g, h_b,
                                block_c=block_c, interpret=interpret)
    return ge_mask_ref(u_t, u_e, s0, p_gb, p_bg, h_g, h_b)
