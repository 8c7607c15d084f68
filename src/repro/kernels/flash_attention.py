"""Flash attention Pallas kernel (TPU target; interpreted on CPU).

Online-softmax tiled attention: for each (batch, q-head, q-block) program
instance, stream KV blocks through VMEM, maintaining the running max ``m``,
normalizer ``l`` and accumulator ``acc``:

    s   = q @ k_j^T * scale           (MXU: block_q x block_k)
    m'  = max(m, rowmax(s))
    p   = exp(s - m')
    acc = acc * exp(m - m') + p @ v_j (MXU: block_q x head_dim)
    l   = l * exp(m - m') + rowsum(p)
    out = acc / l

Block sizes default to 128x128 — MXU-aligned (the systolic array is
128x128; VMEM footprint per instance is
``block_q*dh + 2*block_k*dh + block_q*block_k`` floats ≈ 190 KiB at
dh=128, far under the ~16 MiB/core VMEM budget, leaving room for
double-buffered prefetch of the next KV block).

GQA is handled by folding the group into the q-head grid axis and indexing
the KV head as ``h // group_size`` in the BlockSpec index maps — no
repeated KV materialization in HBM.

Causal + sliding-window masking is applied inside the kernel; fully-masked
KV blocks are skipped via the grid's block-level early-out (mask computed
from block indices).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                 window: Optional[int], block_q: int, block_k: int,
                 seq_k: int):
    qi = pl.program_id(2)
    nk = pl.cdiv(seq_k, block_k)

    q = q_ref[...].astype(jnp.float32) * scale        # (bq, dh)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros_like(q)

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                   # (bq, bk)
        q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
        k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask &= (k_pos < seq_k)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    # causal early-out: KV blocks strictly above the diagonal contribute
    # nothing; stop the streaming loop at the last needed block.
    if causal:
        upper = jnp.minimum(nk, (qi + 1) * block_q // block_k + 1)
    else:
        upper = nk
    lower = 0
    if window is not None:
        lower = jnp.maximum(0, (qi * block_q - window) // block_k)
    m, l, acc = jax.lax.fori_loop(lower, upper, body, (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128):
    """q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh) with H % KV == 0.
    Returns (B, Sq, H, dh). Interpreted on CPU, compiled on TPU
    (:func:`repro.kernels.platform.pallas_call`).
    """
    B, Sq, H, dh = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / math.sqrt(dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # pad seq to block multiples (masked out inside the kernel)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sqp, Skp = q.shape[1], k.shape[1]

    # layout: (B, H, S, dh) so the head is a grid axis
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Sqp // block_q)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=Sk)

    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, dh),
                         lambda b, h, i: (b, h, i, 0)),
            # whole KV stream for this kv-head stays in VMEM-addressable
            # blocks; the kernel dslices block_k chunks out of it
            pl.BlockSpec((None, None, Skp, dh),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, Skp, dh),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dh),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, dh), q.dtype),
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :Sq]
    return out
