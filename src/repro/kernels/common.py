"""Shared kernel-layer policy: division-guard epsilons and backend-aware
lowering resolution.

Every kernel package (`tra_agg`, `packet_mask`, `qfed_reweight`,
`flash_decode`, `uplink_fused`), every pure-jnp oracle in a ``ref.py``
and the engine's reference aggregation path import their numerical
guards from HERE. A kernel and its reference diverging on an epsilon is
exactly the kind of silent per-mode drift the parity tests cannot see
(both sides would be "self-consistent"), so the constants live in one
module and nowhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Guard for aggregate denominators (sums of client weights or of masked
# per-coordinate weights). Must be far below any realistic weight sum so
# it never perturbs a live denominator, only rescues an empty one.
DENOM_EPS = 1e-12

# Guard for rate rescales: observed kept fractions (1/kept_c) and the
# nominal delivery rate (1/(1 - loss_rate)). These divide *probability*
# scales, where 1e-12 would blow a fully-dropped client up by 1e12; the
# looser guard caps the debias multiplier at 1e6.
RATE_EPS = 1e-6


def resolve_lowering(*, gpu_lowerable: bool = False,
                     use_kernel: bool | None = None,
                     interpret: bool | None = None):
    """Resolve ``(use_kernel, interpret)`` from the backend at call time.

    Policy: compile the Pallas kernel wherever a real lowering exists —
    TPU always, GPU only for kernels flagged ``gpu_lowerable`` (pure
    element-wise bodies; kernels relying on Mosaic's sequential-grid
    scratch accumulation or MXU einsum tiling have no Triton lowering).
    On CPU there is no compiled lowering, so the kernel runs in
    interpret mode (correctness/parity work) — callers on a hot path
    should prefer their jnp reference there. On GPU without a lowering
    the jnp reference is the fallback (interpret emulation on GPU buys
    nothing over XLA's fused jnp).

    Either decision can be forced by passing a non-None override; both
    overrides are plumbed through every ``ops.py`` entry point.
    """
    backend = jax.default_backend()
    compiled = backend == "tpu" or (backend == "gpu" and gpu_lowerable)
    if use_kernel is None:
        use_kernel = compiled or backend == "cpu"
    if interpret is None:
        interpret = not compiled
    return use_kernel, interpret


def resolve_interpret(interpret: bool | None = None,
                      gpu_lowerable: bool = False) -> bool:
    """Interpret-only resolution for ``*_call`` kernel entry points whose
    callers decided separately whether to use the kernel at all."""
    if interpret is not None:
        return interpret
    return resolve_lowering(gpu_lowerable=gpu_lowerable)[1]


def tile_budget(table: dict, what: str):
    """The backend's row of a kernel's tiling table. A backend with no
    row is an error: there is no tiling to fall back to."""
    backend = jax.default_backend()
    if backend not in table:
        raise ValueError(f"{what}: no block sizes for backend {backend!r}"
                         f" (have {sorted(table)})")
    return table[backend]


def round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def pad_to(a, shape):
    """Zero-pad ``a`` up to ``shape`` at the end of every axis (blocks
    that do not divide a dimension pad it to whole blocks)."""
    if a.shape == tuple(shape):
        return a
    return jnp.pad(a, [(0, n - k) for k, n in zip(a.shape, shape)])
