"""Model operations and bytes of a DeepSeek-V2 decoder, per part, from
the configuration file's keys (``configs/deepseek-v2-lite-ep8.json``).

Counted as :mod:`.flops` counts them: 2 FLOPs a weight a token, a
training step 3 times the forward pass, no recomputation, attention's
two products over all ``T`` positions (the count that does not halve
for the causal mask); norms, rope, softmax and the sort are left out.
The held experts are counted at the rows they computed: ``held_rows``
assignments a token, on average, of the step's tokens.
"""

from __future__ import annotations

#: bytes of a bfloat16 number
BF16 = 2


def mla_flops(d: int, heads: int, rank: int, nope: int, rope: int,
              v: int, seq: int) -> float:
    """Latent attention, a token: q, latent and k_pe, the up-projection
    of the latent to k_nope and v, the output; scores and the weighted
    sum over ``seq`` positions."""
    weights = (d * heads * (nope + rope) + d * (rank + rope)
               + rank * heads * (nope + v) + heads * v * d)
    return float(2 * weights + 2 * seq * heads * (nope + rope)
                 + 2 * seq * heads * v)


def swiglu_flops(d: int, width: int) -> float:
    """A SwiGLU of ``width`` hidden units, a token (gate, up, down)."""
    return float(2 * 3 * d * width)


def router_flops(d: int, experts: int) -> float:
    return float(2 * d * experts)


def head_flops(d: int, vocab: int) -> float:
    return float(2 * d * vocab)


def forward_flops_per_token(cfg: dict, held_rows: float) -> float:
    """One token's forward pass through the cut model: the dense layers,
    then per expert layer latent attention, the shared experts, the
    router and ``held_rows / n_expert_layers`` held-expert rows, and
    the head."""
    d, seq = cfg["hidden_size"], cfg["block_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    attn = mla_flops(d, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                     cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], seq)
    fe = cfg["moe_intermediate_size"]
    expert_layer = (attn + swiglu_flops(d, cfg["n_shared_experts"] * fe)
                    + router_flops(d, cfg["router_outputs"]))
    return (dense * (attn + swiglu_flops(d, cfg["intermediate_size"]))
            + (layers - dense) * expert_layer
            + held_rows * swiglu_flops(d, fe)
            + head_flops(d, cfg["vocab_size"]))


def train_flops_per_token(cfg: dict, held_rows: float) -> float:
    """Forward and backward: 3 x the forward pass."""
    return 3.0 * forward_flops_per_token(cfg, held_rows)


def grouped_matmul_bytes(rows: int, d: int, width: int,
                         experts: int) -> float:
    """Forward bytes of the held experts' three grouped products over
    ``rows`` sorted rows: the rows read twice (gate, up), the weights,
    the two hidden products written and read back, the output written."""
    return float(BF16 * (2 * rows * d + 3 * experts * d * width
                         + 4 * rows * width + rows * d))
