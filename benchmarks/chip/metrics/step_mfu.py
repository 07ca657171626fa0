"""step_mfu (%, device trace): useful model FLOPs of the traced window
(live rows at their true lengths) over the device time of all step programs
at the chip's bf16 peak."""
from chipbench import derived


def read(ctx):
    return derived.step_mfu_pct(ctx)
