"""Median host time of the fairness eval after each block, from the
benchmark's span around it; the span ends when the report is on the
host."""
import statistics


def read(ctx):
    if not ctx.eval_s:
        return None
    return statistics.median(ctx.eval_s) * 1e3
