"""Device time of the prefill programs in the trace over their count
(model step layer)."""


def read(ctx):
    return ctx.program_ms("prefill")
