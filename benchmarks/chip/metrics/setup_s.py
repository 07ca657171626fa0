"""setup_s (s, host clock): process start to the opening of the measured
window: weights made on the device, the engine and its KV pool, and the
warm-up of every program the window dispatches (compiles, on a cold cache).
"""


def read(ctx):
    return ctx.setup_s
