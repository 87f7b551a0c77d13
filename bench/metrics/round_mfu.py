"""Model FLOP utilization of the block program: the local-training
FLOPs of the client updates its traced runs made (the model module's
``train_flops_per_update``: for a model trained whole, 6 D per training
sample, forward and backward with no recompute; the round-start loss and
the eval are left out), over the program's device time in the trace
and the chip's bf16 peak."""
from bench import models


def read(ctx):
    t = ctx.trace
    if ctx.peaks is None or t is None or not t["block_program_runs"] \
            or t["block_program_s"] <= 0.0:
        return None
    m, fl = ctx.config["model"], ctx.config["fl"]
    per_update = models.load(m["kind"]).train_flops_per_update(m, fl)
    updates = t["block_program_runs"] * ctx.k * ctx.C
    return 100.0 * per_update * updates / t["block_program_s"] \
        / ctx.peaks["bf16_flops_per_s"]
