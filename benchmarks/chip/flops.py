"""Model operations per token, from a configuration's shapes.

Counted as the algorithm requires them, with no recomputation: a training
step is the forward pass times 3 (forward, and backward at twice the
forward). Per token, the forward pass of a decoder of ``n_layer`` blocks
at width ``d`` with ``d_ff`` hidden units and context ``T`` costs
2 FLOPs per weight of the blocks' matrices (4 d^2 attention projections,
2 d d_ff MLP), 2 d V for the output head (tied or not, it is a matmul),
and 4 T d per layer for the two attention products (scores and the
weighted sum over all T positions, the usual count that does not halve
for the causal mask). Embedding lookups, norms and the softmax are left
out.
"""

from __future__ import annotations


def decoder_forward_flops_per_token(n_layer: int, d: int, d_ff: int,
                                    vocab: int, seq: int) -> float:
    block_weights = 4 * d * d + 2 * d * d_ff
    return float(2 * n_layer * block_weights + 2 * d * vocab
                 + 4 * n_layer * seq * d)


def decoder_train_flops_per_token(n_layer: int, d: int, d_ff: int,
                                  vocab: int, seq: int) -> float:
    """Forward and backward: 3 x the forward pass."""
    return 3.0 * decoder_forward_flops_per_token(n_layer, d, d_ff, vocab,
                                                 seq)
