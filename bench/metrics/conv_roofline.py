"""Convolutions' share of their roofline: the least time the chip could
spend on every convolution pass of the traced steps (the larger of
operations over peak and bytes over bandwidth, from shapes, per chip) over
the device time of the trace's convolution ops, per chip."""


def read(ctx):
    if ctx.trace.conv_s <= 0:
        return None
    least = ctx.flops.conv_roofline_s(
        ctx.model, ctx.batch, ctx.peak[ctx.cell.config["peak"]],
        ctx.peak["hbm_bytes_per_s"], ctx.itemsize) * ctx.steps / ctx.chips
    return 100.0 * least / ctx.trace.conv_s
