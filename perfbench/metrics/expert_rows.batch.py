"""Mean rows routed to one held expert in one MoE layer of one batched
decode step, over the window's steps that decoded: the engine step log's
``expert_rows`` (rows routed to a held expert, summed over held experts
and MoE layers, returned by the decode program with its tokens) over the
held experts and the MoE layers (model step layer).  None for a program
that keeps no such counter or holds no experts."""


def read(ctx):
    eng = ctx.driver.eng
    log = getattr(getattr(eng, "metrics", None), "steps", None)
    cfg = getattr(getattr(eng, "model", None), "cfg", None)
    held = len(getattr(cfg, "experts_held", ()) or ())
    if log is None or not held:
        return None
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    t0 = ctx.driver.t0
    t1 = t0 + ctx.readings.window_s
    steps = [r for r in log.records
             if r.decode_rows and t0 <= r.t_begin and r.t_end <= t1]
    if not steps or not moe_layers:
        return None
    rows = sum(getattr(r, "expert_rows", 0) for r in steps)
    return rows / (len(steps) * held * moe_layers)
