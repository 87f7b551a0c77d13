"""Roofline share of the ``uplink_fused`` kernel (memory bound: the
uploads, the error-feedback rows read and written, the aggregate)."""
from bench import flops
from bench.metrics import _roofline


def read(ctx):
    fl = ctx.config["fl"]
    c, p, f = _roofline.shape(ctx)
    per_call = flops.uplink_fused(c, p, f, ef=fl["error_feedback"],
                                  want_ssq=fl["algo"] == "qfedavg")
    return _roofline.share(ctx, "uplink_fused", per_call)
