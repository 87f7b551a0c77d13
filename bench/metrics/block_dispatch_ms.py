"""Mean host time of the block program's call, ``fl.block.dispatch``:
flattening the context and state, donating the state and the enqueue
(the device's run is not in it)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms("fl.block.dispatch")
