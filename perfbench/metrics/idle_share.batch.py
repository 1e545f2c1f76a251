"""Share of the traced window in which no operation ran on the device
(device layer)."""


def read(ctx):
    return ctx.idle_share()
