"""90th percentile of the wait from when a request was due to when the
engine admitted it, over the requests due in the window; one still waiting
at the close counts with its wait so far (engine layer)."""
from harness.drive import percentile


def read(ctx):
    q = ctx.readings.queue_ms
    return percentile(q, 90) if q else None
