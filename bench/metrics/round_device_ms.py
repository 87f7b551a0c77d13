"""Device time of the block program per round: the program's runs in
the traced window (``XLA Modules`` events), over the rounds they ran."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["block_program_runs"]:
        return None
    return t["block_program_s"] / (t["block_program_runs"] * ctx.k) * 1e3
