"""Mean length of the KV manager's host pass in a tick: the program's
``repro:kv.tick`` spans (``PagedController``'s freeze, swap-out and
swap-in decisions) that start in the window, ms."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.span_ms("kv.tick")
