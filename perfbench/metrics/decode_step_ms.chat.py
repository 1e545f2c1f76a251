"""Device time of the decode programs in the trace over their count
(model step layer)."""


def read(ctx):
    return ctx.program_ms("decode")
