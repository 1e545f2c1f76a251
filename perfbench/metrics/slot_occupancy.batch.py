"""Tokens emitted per decode step over the engine slots, for the steps of
the window that decoded (engine layer)."""


def read(ctx):
    r = ctx.readings
    if not r.decode_steps:
        return None
    return 100.0 * r.decode_tokens / (r.decode_steps * r.slots)
