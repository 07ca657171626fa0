"""prefill_row_use (%, program counter): share of the tokens the prefill
dispatches computed (rows x width) that were live prompt tokens,
EngineStats.prefill_tokens over prefill_row_tokens. None where the program
keeps no such counter."""


def read(ctx):
    rows = ctx.stats.get("prefill_row_tokens", 0)
    return 100.0 * ctx.stats["prefill_tokens"] / rows if rows else None
