"""paged_prefill_attention_roofline (%, device trace): as the decode
kernel's, for the paged prefill kernel and the prompt rows it attended."""
from chipbench import derived


def read(ctx):
    return derived.roofline_pct(ctx, "paged_prefill_attention")
