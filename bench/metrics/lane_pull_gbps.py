"""Rate of a tick's device-to-host pull of the wave's lanes: the
``bytes`` stat of the program's ``repro:engine.pull_lanes`` spans in the
window over their summed length, GB/s."""


def read(ctx):
    return None if ctx.trace is None \
        else ctx.trace.span_gbps("engine.pull_lanes")
