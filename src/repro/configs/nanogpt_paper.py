"""NanoGPT config from the paper's Section J: vocab 50304, block 512,
6 layers, 6 heads, d_model 384 — used for the R-estimation study and the
K.5 Sync-vs-Async comparison."""

from .base import AttnConfig, Block, ModelConfig, Stage

CONFIG = ModelConfig(
    name="nanogpt-paper",
    arch_type="dense",
    d_model=384,
    vocab_size=50304,
    d_ff=1536,
    stages=(Stage(pattern=(Block("attn", "mlp"),), repeats=6),),
    attn=AttnConfig(num_heads=6, num_kv_heads=6, head_dim=64,
                    rope_theta=None, causal=True),
    pos_embed="learned",
    mlp_act="gelu",
    norm="layernorm",
    tie_embeddings=True,
    embed_scale=True,
    max_seq_len=512,
    citation="github.com/karpathy/nanoGPT (paper §J)",
)
