"""device_idle (%, device trace): share of the traced window's pending-work
time (the engine held requests) in which no operation ran on the device."""


def read(ctx):
    t = ctx.trace
    return 100.0 * t.idle_pending_s / t.pending_s if t and t.pending_s else None
