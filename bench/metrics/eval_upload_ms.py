"""Mean host time of the eval set's copy to the device in each fairness
eval, ``fl.eval.upload``."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms("fl.eval.upload")
