"""Device time of the optimizer a step: the ops that the program's
``optimizer`` scope roots (the clip and the update), per step of the traced
window and per chip, in ms (``bench/scopes.py``)."""


def read(ctx):
    return ctx.layers.readings().get("optimizer_ms")
