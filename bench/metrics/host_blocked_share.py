"""Share of the window the engine's host side spends blocked in
host<->device transfers (``TransferStats.blocked_s``, a host clock around
the blocking ``device_get``/``device_put``), %."""


def read(ctx):
    b = ctx.run.after["blocked_s"] - ctx.run.before["blocked_s"]
    return 100.0 * b / ctx.window_s
