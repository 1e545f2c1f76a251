"""Mean time an admission holds the engine, per request admitted inside
the window: the engine step log's admission phases (the bookkeeping, the
prefill's dispatch, the wait for its first token and the page write's
dispatch), summed over the window's steps that admitted, over the
requests they admitted.  Decoding requests wait that long for their next
token (engine layer).  None for a program that keeps no step log."""

PHASES = ("admission", "prefill", "prefill.wait", "page_write")


def read(ctx):
    log = getattr(getattr(ctx.driver.eng, "metrics", None), "steps", None)
    if log is None:
        return None
    t0 = ctx.driver.t0
    t1 = t0 + ctx.readings.window_s
    steps = [r for r in log.records
             if r.admitted and t0 <= r.t_begin and r.t_end <= t1]
    admitted = sum(r.admitted for r in steps)
    if not admitted:
        return None
    return 1e3 * sum(r.phases.get(p, 0.0) for r in steps
                     for p in PHASES) / admitted
