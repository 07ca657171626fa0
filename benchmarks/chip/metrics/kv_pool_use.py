"""kv_pool_use (%, program counter): share of the KV pool's capacity that
the live slots held, summed over the window's decode dispatches,
EngineStats.kv_live_tokens over kv_pool_tokens. None where the program
keeps no such counter."""


def read(ctx):
    pool = ctx.stats.get("kv_pool_tokens", 0)
    return 100.0 * ctx.stats["kv_live_tokens"] / pool if pool else None
