"""Roofline share of one kernel: the least time the chip could take for
the kernel's calls in the traced window (the larger of FLOPs over the
FLOP peak and bytes over the HBM peak, from ``bench/flops.py``), over
the kernel's device time there."""
from bench import flops


def share(ctx, kernel: str, per_call):
    t = ctx.trace
    if t is None or ctx.peaks is None or not t["kernel_calls"].get(kernel):
        return None
    secs = t["kernel_s"][kernel]
    if secs <= 0.0:
        return None
    f, b = per_call
    least = max(f / ctx.peaks["bf16_flops_per_s"],
                b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * t["kernel_calls"][kernel] / secs


def shape(ctx):
    """(C, P, F) of one uplink call: clients, packets, floats a packet."""
    m, fl = ctx.config["model"], ctx.config["fl"]
    d = flops.mlp_params(m["d_in"], m["d_hidden"], m["n_classes"])
    f = fl["tra"]["packet_floats"]
    return ctx.C, -(-d // f), f
