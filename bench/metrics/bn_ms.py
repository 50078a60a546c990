"""Device time of BatchNorm a step: the ops that the program's
``batchnorm`` scope roots and that neither are nor fuse a convolution
(those count with the convolutions), per step of the traced window and per
chip, in ms (``bench/scopes.py``)."""


def read(ctx):
    return ctx.layers.readings().get("bn_ms")
