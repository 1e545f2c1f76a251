"""Mean host time of the engine steps that ran inside the window: a step's
span less the seconds it waited on the device, read from the engine's own
step log (``Engine.metrics.steps``); during it the chip has nothing new to
run (engine layer).  None for a program that keeps no step log."""


def read(ctx):
    log = getattr(getattr(ctx.driver.eng, "metrics", None), "steps", None)
    if log is None:
        return None
    t0 = ctx.driver.t0
    t1 = t0 + ctx.readings.window_s
    steps = [r for r in log.records if t0 <= r.t_begin and r.t_end <= t1]
    if not steps:
        return None
    return 1e3 * sum(r.t_end - r.t_begin - r.wait_s
                     for r in steps) / len(steps)
