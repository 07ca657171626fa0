"""paged_mla_prefill_attention_roofline (%, device trace): as the latent
decode kernel's, for the paged latent prefill kernel and the prompt rows it
attended."""
from chipbench import derived


def read(ctx):
    return derived.roofline_pct(ctx, "paged_mla_prefill_attention")
