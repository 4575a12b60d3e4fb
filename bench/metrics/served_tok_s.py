"""Tokens streamed to clients in the window, net of rewinds, over the
window: the end-to-end decode rate, read here because runs of one cell
split between two rates (which page-boundary ticks fall inside the window
shifts with set-up's timing).  Client-side host clock, tokens/s."""


def read(ctx):
    return ctx.W.decode_tok_s(ctx.run.recs, ctx.run.t0, ctx.run.t1)
