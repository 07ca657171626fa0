"""decode_rows_mean (rows, program counter): rows decoded per decode step
in the window, EngineStats.decode_tokens over decode_syncs."""


def read(ctx):
    n = ctx.stats.get("decode_syncs", 0)
    return ctx.stats["decode_tokens"] / n if n else None
