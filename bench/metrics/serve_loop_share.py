"""Share of the window the serve loop (``AsyncServingEngine``) spends
outside ``Scheduler.step``: applying ops, pumping streams, idling.
Harness span around each ``Scheduler.step`` call, clipped to the window,
host clock, %."""


def read(ctx):
    t0, t1 = ctx.run.t0, ctx.run.t1
    inside = sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in ctx.run.steps)
    if not ctx.run.steps:
        return None
    return 100.0 * (1.0 - inside / (t1 - t0))
