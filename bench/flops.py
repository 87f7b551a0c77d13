"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of the roofline and utilization metrics: what
the work takes at the least, not what the program happens to move
(padding to tile sizes, re-reads and copies count against the program,
as a lower share). All arrays are float32 (4 bytes).
"""
from __future__ import annotations

F32 = 4


def mlp_params(d_in: int, d_hidden: int, n_classes: int) -> int:
    """D: weights and biases of the d_in-d_hidden-n_classes MLP."""
    return d_in * d_hidden + d_hidden + d_hidden * n_classes + n_classes


def train_flops_per_update(d: int, local_steps: int, batch: int) -> float:
    """Model FLOPs of one client update: forward and backward of every
    local training sample, 6 D per sample, with no recompute. The loss
    a client reports at the round's start, and the eval, are left out."""
    return 6.0 * d * local_steps * batch


def uplink_fused(c: int, p: int, f: int, *, ef: bool,
                 want_ssq: bool) -> tuple:
    """(flops, bytes) of one ``uplink_fused`` call over C clients x P
    packets x F floats: read the uploads, the delivery mask and the
    per-client scales, read and write the error-feedback rows, write the
    aggregate (and the per-client masked squared norms)."""
    n = c * p * f
    flops = n * (2 + (1 if ef else 0) + (2 if ef else 0)
                 + (3 if want_ssq else 0))
    by = n * F32 * (1 + (2 if ef else 0))
    by += c * p * F32 + c * F32 + F32               # mask, scales, den
    by += p * f * F32                               # aggregate
    if want_ssq:
        by += c * p * F32
    return float(flops), float(by)


def robust_agg(c: int, p: int, f: int, *, ef: bool,
               trim_k: int) -> tuple:
    """(flops, bytes) of one ``robust_agg`` call: read the uploads, the
    delivery mask and the per-client scales (and trim scales and
    validity), read and write the error-feedback rows, screen every
    float, write the aggregate. The trimmed mean extracts ``trim_k``
    minima and maxima over the clients of every coordinate."""
    n = c * p * f
    flops = n * (3 + (1 if ef else 0) + (2 if ef else 0)
                 + (4 * trim_k + 2 if trim_k else 0))
    by = n * F32 * (1 + (2 if ef else 0))
    cols = 2 + (2 if trim_k else 0)                 # q, w; g, w_pos
    by += c * p * F32 + c * cols * F32 + 3 * F32
    by += p * f * F32                               # aggregate
    return float(flops), float(by)
