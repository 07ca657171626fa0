"""paged_mla_decode_attention_roofline (%, device trace): the paged latent
decode kernel's least time at the chip's peaks (the larger of FLOPs over
peak and bytes over bandwidth, for live rows at their true lengths, in the
absorbed form) over its device time in the traced window."""
from chipbench import derived


def read(ctx):
    return derived.roofline_pct(ctx, "paged_mla_decode_attention")
