"""itl_p99_s (s, host clock): 99th percentile of every gap between
consecutive tokens returned to the judged (chat) requests, the later token
returned inside the window."""
from chipbench import derived, stats


def read(ctx):
    gaps = [g for x in derived.judged(ctx)
            for g in x.gaps(until_s=ctx.window.seconds)]
    return stats.percentile(gaps, 99)
