"""90th percentile of the time to first token, from when a request was due
to the host stamp after the step that returned its first token, over the
requests due in the window; one unserved at the close counts with its wait
so far (engine layer)."""
from harness.drive import percentile


def read(ctx):
    t = ctx.readings.ttft_ms
    return percentile(t, 90) if t else None
