"""Mean host time of ``fl.block.copy``: the wait for the block program
and the copy of its logs to the host, one array at a time."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms("fl.block.copy")
