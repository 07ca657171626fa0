"""Quantities several metric readers share, computed from what one run
recorded: the requests a stream's limits judge, and the work of the
dispatches made inside the measured window."""
from __future__ import annotations

from chipbench import flops


def judged(ctx) -> list:
    """Requests due in the window whose stream states limits (chat)."""
    return [x for x in ctx.window.timed.values() if x.stream in ctx.slo]


def _work(ctx) -> dict:
    m = ctx.dims
    rows = list(ctx.dispatches.rows(ctx.first_call, ctx.close_call))
    pre = [(start, len(t)) for kind, _, start, t in rows if kind == "prefill"]
    dec = [start + 1 for kind, _, start, _t in rows if kind == "decode"]
    pf, pb = flops.prefill_attention(m, pre)
    df, db = flops.decode_attention(m, dec)
    return {"prefill_tokens": sum(c for _, c in pre),
            "decode_tokens": len(dec),
            "step_flops": flops.step_flops(m, prefill_rows=pre,
                                           decode_lengths=dec),
            "prefill_attention": (m.layers * pf, m.layers * pb),
            "decode_attention": (m.layers * df, m.layers * db)}


def window_work(ctx) -> dict:
    """Useful work of the window's dispatches: tokens, whole-step FLOPs,
    and each attention kernel's (FLOPs, bytes) over all layers."""
    if not hasattr(ctx, "_work"):
        ctx._work = _work(ctx)
    return ctx._work


def roofline_pct(ctx, kernel: str, work_key: str):
    """The kernel's least time at the chip's peaks over its device time in
    the trace, in percent; None where the trace holds no such kernel."""
    t = ctx.trace.kernel_s.get(kernel) if ctx.trace else None
    if not t:
        return None
    fl, by = window_work(ctx)[work_key]
    if fl == 0 and by == 0:
        return None
    least = max(fl / ctx.peaks["bf16_flops_per_s"],
                by / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def step_mfu_pct(ctx):
    """Useful model FLOPs of the window over the device time of all step
    programs at the chip's bf16 peak, in percent."""
    if not ctx.trace:
        return None
    t = ctx.trace.program_s.get("prefill", 0.0) + \
        ctx.trace.program_s.get("decode", 0.0)
    if not t:
        return None
    return 100.0 * window_work(ctx)["step_flops"] / (
        t * ctx.peaks["bf16_flops_per_s"])
