"""host_gap_ms (ms, host clock): mean host time from a token fetch after
which the engine still held work to its next device dispatch,
1000 * EngineStats.host_gap_s / host_gaps over the window. None where the
program keeps no such counter."""


def read(ctx):
    n = ctx.stats.get("host_gaps", 0)
    return 1000.0 * ctx.stats["host_gap_s"] / n if n else None
