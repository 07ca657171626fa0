"""tokens_per_s (tokens/s, host clock): every output token returned inside
the window, over the window's length."""


def read(ctx):
    n = sum(1 for x in ctx.window.timed.values() for t in x.token_s
            if t <= ctx.window.seconds)
    return n / ctx.window.seconds
