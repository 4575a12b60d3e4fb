"""Mean length of a page-boundary tick: the program's ``repro:engine.tick``
spans that start in the window (pull, unpack, the controller's host pass,
push and remaps of one wave of lanes), host clock on the profiler's
timeline, ms."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.span_ms("engine.tick")
