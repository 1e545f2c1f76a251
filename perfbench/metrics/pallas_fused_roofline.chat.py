"""Roofline share of the fused TiledCSC kernel over the traced window
(kernels layer): the least time of the calls made over its device time."""


def read(ctx):
    return ctx.roofline("pallas_fused")
