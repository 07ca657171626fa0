"""prefill_ms_per_ktok (ms, device trace): device time of the prefill step
programs in the traced window per 1,000 prompt tokens they prefilled."""
from chipbench import derived


def read(ctx):
    t = ctx.trace.program_s.get("prefill") if ctx.trace else None
    n = derived.window_work(ctx)["prefill_tokens"] if t else 0
    return 1e6 * t / n if n else None
