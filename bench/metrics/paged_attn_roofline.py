"""The paged decode attention kernel's share of its roofline: the least
time its calls in the window need (``bench/work/paged_decode_attn.py``:
K/V of the tokens they can see, q, output and page tables, at the chip's
peaks; memory bound here) over the kernel's device time in the trace, %.
Counting needed bytes, not fetched ones, lets a change that skips frozen
or unmapped pages raise the share toward 100%."""


def read(ctx):
    if ctx.trace is None or not ctx.run.visible:
        return None
    s, calls = ctx.trace.ops_matching(ctx.work.paged_decode_attn.OP)
    if not calls or s <= 0:
        return None
    e = ctx.engine
    w = ctx.work.paged_decode_attn.work(
        ctx.config, visible=ctx.run.visible, calls=calls,
        lanes=e["n_lanes"], pages=e["P_total"], page=e["page"])
    t, _ = ctx.work.paged_decode_attn.least_seconds(w, ctx.peaks)
    return 100.0 * t / s
