"""Roofline share of the ``robust_agg`` kernel (memory bound: the
uploads, the error-feedback rows read and written, the aggregate)."""
from bench import flops
from bench.metrics import _roofline


def read(ctx):
    fl = ctx.config["fl"]
    c, p, f = _roofline.shape(ctx)
    per_call = flops.robust_agg(c, p, f, ef=fl["error_feedback"],
                                trim_k=fl["defense"]["trim_k"])
    return _roofline.share(ctx, "robust_agg", per_call)
