"""Roofline share of the Pallas ``latency_hist`` kernel: the least time
its shapes need on this chip (``chipbench.roofline``) over the device
time of the kernel's own ops in the trace."""

from chipbench import roofline

KERNEL = "latency_hist"


def read(ctx):
    shapes = getattr(ctx.engine, "hist_shape", None)
    if ctx.summary is None or shapes is None or ctx.peak is None:
        return None
    seconds = ctx.summary.op_seconds(KERNEL)
    if seconds <= 0:
        return None
    lanes, samples, bins = shapes
    ops, bytes_ = roofline.latency_hist_cost(lanes, samples, bins)
    pct, _ = roofline.share(ops * ctx.answers, bytes_ * ctx.answers,
                            seconds, ctx.peak)
    return pct
