"""Mean length of an engine step that holds no boundary tick: the
program's ``repro:engine.step`` spans in the window with no
``repro:engine.tick`` inside them on their thread, ms."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.plain_step_ms()
