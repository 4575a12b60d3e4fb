"""The whole decode step's share of the chip's bf16 peak: the operations
the window's decode steps need (``bench/work/decode_step.py``: weight
matmuls for the live lanes, attention over the tokens each can see) over
the device time of the decode-step programs in the trace times the peak,
%.  Bounds any kernel's roofline gain on the step.  The decode step is the
program that runs the paged attention kernel (the engine jits a
``functools.partial``, so the program's own name is ``jit__unknown``)."""


def read(ctx):
    if ctx.trace is None or not ctx.run.lane_steps:
        return None
    s, n = ctx.trace.modules_holding(ctx.work.paged_decode_attn.OP)
    if not n or s <= 0:
        return None
    f = ctx.work.decode_step.flops(ctx.config, ctx.run.lane_steps,
                                   ctx.run.visible)
    return 100.0 * f / (s * ctx.peaks["bf16_flops_per_s"])
