"""95th percentile of every gap between consecutive tokens of a request
inside the window, pooled over requests: the decode steps that carry a
prefill (engine layer)."""
from harness.drive import percentile


def read(ctx):
    g = ctx.readings.itl_ms
    return percentile(g, 95) if g else None
