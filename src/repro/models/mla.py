"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per token, with ``H`` heads (no query compression, as in V2-Lite)::

    q            = x W_q                    (H, nope + rope)
    [c_kv, k_pe] = x W_kva                  (kv_lora_rank), (rope)
    c_kv         = RMSNorm(c_kv)
    [k_nope, v]  = c_kv W_kvb               (H, nope), (H, v)
    k            = [k_nope, k_pe]           k_pe: one head, shared by all

YaRN rope on ``q_pe`` and ``k_pe``, then a causal softmax in float32 of
``q . k * softmax_scale`` and ``o = softmax . v``, then ``W_o``. The rope
rotates the pairs ``(i, i + rope/2)``: DeepSeek's interleaved pairs
``(2i, 2i + 1)`` up to a fixed permutation of ``W_q``'s and ``W_kva``'s
rope columns.

Attention is computed in query blocks of :data:`Q_BLOCK` rows, each
against the keys up to its last row, and each block is recomputed in the
backward pass: no ``(B, H, S, S)`` tensor exists whole, forward or
backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .layers import dense_init, norm_apply

__all__ = ["init_mla", "mla_apply", "yarn_inv_freq", "softmax_scale",
           "Q_BLOCK"]

#: query rows per attention block
Q_BLOCK = 256


def init_mla(key, d: int, num_heads: int, mla, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    qk = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    r = mla.kv_lora_rank
    return {
        "wq": dense_init(ks[0], (d, num_heads * qk), dtype=dtype),
        "wkv_a": dense_init(ks[1], (d, r + mla.qk_rope_head_dim),
                            dtype=dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": dense_init(ks[2], (r, num_heads * (mla.qk_nope_head_dim
                                                    + mla.v_head_dim)),
                            dtype=dtype),
        "wo": dense_init(ks[3], (num_heads * mla.v_head_dim, d),
                         dtype=dtype),
    }


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(mla) -> np.ndarray:
    """The rope's ``rope/2`` inverse frequencies under YaRN: the original
    frequencies where a dimension turns more than ``beta_fast`` times over
    the original context, those divided by the factor where it turns fewer
    than ``beta_slow`` times, a linear ramp between."""
    dim, base = mla.qk_rope_head_dim, mla.rope_theta
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if mla.rope_factor <= 1:
        return extra

    def corr_dim(rotations):
        return (dim * math.log(mla.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(mla.beta_fast)), 0)
    high = min(math.ceil(corr_dim(mla.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / mla.rope_factor * (1.0 - mask) + extra * mask


def softmax_scale(mla) -> float:
    """``mscale(factor, mscale_all_dim)**2 / sqrt(nope + rope)``."""
    m = _yarn_mscale(mla.rope_factor, mla.mscale_all_dim)
    return m * m / math.sqrt(mla.qk_nope_head_dim + mla.qk_rope_head_dim)


def _rope_tables(mla, S: int):
    """cos and sin, ``(S, rope/2)`` float32, times YaRN's mscale ratio."""
    ang = np.arange(S, dtype=np.float64)[:, None] * yarn_inv_freq(mla)
    m = (_yarn_mscale(mla.rope_factor, mla.mscale)
         / _yarn_mscale(mla.rope_factor, mla.mscale_all_dim))
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def _rotate(x, cos, sin):
    """x: (B, S, h, rope); pairs (i, i + rope/2) rotated by position."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _attend_block(q, k, v, q0: int, m: int, scale: float):
    """Rows ``q0 ..`` of the causal attention, against keys ``0 .. m``:
    q (B, n, H, dq), k (B, S, H, dq), v (B, S, H, dv). The whole k and v
    come in, so that the backward pass keeps one copy of them, not one
    slice per block."""
    n = q.shape[1]
    k, v = k[:, :m], v[:, :m]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    ok = (q0 + jnp.arange(n))[:, None] >= jnp.arange(m)[None, :]
    s = jnp.where(ok[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def causal_attention(q, k, v, scale: float, block: int = Q_BLOCK):
    """Causal softmax attention in query blocks: block ``[s0, s1)`` sees
    keys ``[0, s1)``. q, k: (B, S, H, dq); v: (B, S, H, dv)."""
    S = q.shape[1]
    outs = [_attend_block(q[:, s0:s0 + block], k, v, s0,
                          min(s0 + block, S), scale)
            for s0 in range(0, S, block)]
    return jnp.concatenate(outs, axis=1)


def mla_apply(p: dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """(B, S, D) -> (B, S, D), training and prefill (no cache)."""
    a, H = cfg.mla, cfg.attn.num_heads
    B, S, _ = x.shape
    nope, rope, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    with jax.named_scope("mla"):
        q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
        kva = x @ p["wkv_a"]
        c_kv = norm_apply(p["kv_norm"], kva[..., :r], cfg.norm_eps)
        kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + a.v_head_dim)
        cos, sin = _rope_tables(a, S)
        q_pe = _rotate(q[..., nope:], cos, sin)
        k_pe = _rotate(kva[..., None, r:], cos, sin)        # (B, S, 1, rope)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))],
            axis=-1)
        o = causal_attention(q, k, kv[..., nope:], softmax_scale(a))
        return o.reshape(B, S, H * a.v_head_dim) @ p["wo"]
