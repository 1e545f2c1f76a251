"""Host seconds the program spent pruning and packing the weights, each
leaf ending in block_until_ready (set-up layer)."""


def read(ctx):
    return ctx.pack_s
