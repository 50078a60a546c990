"""The training loop's host work a step: the mean time from the end of one
step's ``train.wait`` span to the start of the next step's, over the traced
steps, in ms (``bench/scopes.loop_host_s``). A loop that blocks on each
step's loss leaves the chip idle through it."""


def read(ctx):
    return ctx.layers.readings().get("loop_host_ms")
