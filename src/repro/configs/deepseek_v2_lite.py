"""deepseek-v2-lite-ep8 [moe]: DeepSeek-V2-Lite at its published widths,
cut to one chip's share of an expert-parallel deployment in which 8 chips
share each layer. [huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json,
arXiv:2405.04434]

Published: 27 layers at d 2048 with 16 heads of multi-head latent
attention (kv_lora_rank 512, no q compression, nope 128, rope 64, v 128,
YaRN factor 40 over 4096 original positions, mscale = mscale_all_dim =
0.707), a first dense layer of width 10944, then 26 layers of 64 routed
experts of width 1408 (top 6 of a softmax, not renormalised, scaling 1)
beside 2 shared experts, vocabulary 102400, untied head, RMSNorm eps 1e-6.

The chip's share: 8 of the 64 experts of each layer (0-7), an eighth of
the vocabulary (12800 ids), and the first dense layer plus 5 expert
layers; the layers left out would lie on further chips as pipeline
stages. The router keeps all 64 outputs and its 6 experts per token.
Every layer is recomputed in the backward pass, to fit 4096-token rows.
"""

from .base import (AttnConfig, Block, MLAConfig, ModelConfig, MoEConfig,
                   Stage)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-ep8",
    arch_type="moe",
    d_model=2048,
    vocab_size=12800,      # 102400 / 8: the chip's slice
    d_ff=10944,            # the first, dense layer
    stages=(
        Stage(pattern=(Block("mla", "mlp"),), repeats=1),
        Stage(pattern=(Block("mla", "moe"),), repeats=5),   # of 26
    ),
    attn=AttnConfig(num_heads=16, num_kv_heads=16, head_dim=128,
                    rope_theta=10000.0, causal=True),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
                  rope_factor=40.0, original_max_position=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, experts_per_token=6, d_expert=1408,
                  num_shared_experts=2, d_shared=1408,
                  router_aux_weight=0.001, norm_topk_prob=False,
                  experts_held=8, held_offset=0),
    mlp_act="swiglu",
    norm="rmsnorm",
    norm_eps=1e-6,
    tie_embeddings=False,
    remat=True,
    max_seq_len=163840,
    citation="huggingface.co/deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434",
)
