"""The whole step's share of the chips' peak over the traced steps: the
model operations of those steps over (traced window * chips * peak). A
kernel taken off the path leaves its roofline silent; this still bounds it."""


def read(ctx):
    flops = ctx.step_flops * ctx.steps
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak[ctx.cell.config["peak"]])
