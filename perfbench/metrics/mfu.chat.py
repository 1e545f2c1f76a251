"""Model operations of the tokens processed in the window over the window,
as a share of the bf16 peak (model step layer)."""


def read(ctx):
    return ctx.mfu()
