"""Rate of a tick's host-to-device push of the wave's lanes: the
``bytes`` stat of the program's ``repro:engine.push_lanes`` spans with
``kv``=1 (the push that carries K/V pages, not an install's) in the
window over their summed length, GB/s."""


def read(ctx):
    return None if ctx.trace is None \
        else ctx.trace.span_gbps("engine.push_lanes", kv=1)
