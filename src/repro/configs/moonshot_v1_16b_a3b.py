"""moonshot-v1-16b-a3b — Moonlight-16B-A3B. [hf:moonshotai/Moonlight-16B-A3B; hf]

DeepSeek-V3 block (``model_type: deepseek_v3``) at small widths: 27 layers,
d_model 2048, vocab 163,840, untied head, RMSNorm eps 1e-5, rope_theta
50,000. Latent attention (MLA): 16 heads, no q LoRA (q is one projection to
16 x (128 + 64)), a 512-wide RMS-normed KV latent and one 64-wide rotary
key shared by all heads, values of 128. Layer 0 is dense (SwiGLU 11,264,
``first_k_dense_replace`` 1); layers 1-26 route each token to 6 of 64
experts of 1,408 by sigmoid scores with a correction bias used only for the
choice (``noaux_tc``, one group), the chosen scores normalised and scaled
by 2.446, beside 2 shared experts of 1,408. No token is dropped.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163_840,
    rope_theta=50_000.0,
    num_experts=64,
    num_experts_per_token=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    scoring_func="sigmoid",
    routed_scaling_factor=2.446,
    first_k_dense_replace=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    source="hf:moonshotai/Moonlight-16B-A3B",
    notes="EP: 4 experts per model shard on the 16-way axis",
)
