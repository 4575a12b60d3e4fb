"""KV manager traffic: pages swapped out to and in from the host store
(``PagedController.n_swap_out`` + ``n_swap_in``) per engine decode step
in the window."""


def read(ctx):
    a, b = ctx.run.after, ctx.run.before
    steps = a["wall_step"] - b["wall_step"]
    if not steps:
        return None
    return ((a["swap_out"] - b["swap_out"]) + (a["swap_in"] - b["swap_in"])) \
        / steps
