"""RWKV-6 chunked linear-recurrence Pallas kernel (TPU target).

One program instance per (batch, head): the kernel walks the sequence in
chunks of ``chunk`` tokens, carrying the (K, V) state in VMEM scratch. Per
chunk (mirroring models/ssm.chunked_scan exactly, RWKV convention):

    P      = cumprod(w) along the chunk (inclusive)        [VPU]
    y_in   = (r * P/w) @ S                                 [MXU KxV]
    att    = ((r * P/w) @ (k/P)^T) * strict_lower + diag(u·r·k)
    y      = y_in + att @ v                                [MXU cxc, cxV]
    S      = diag(P_tot) S + ((P_tot/P) * k)^T @ v         [MXU Kxc @ cxV]

VMEM footprint per instance: chunk x K x 5 + K x V + chunk x chunk floats
= 64x64x5 + 64x64 + 64x64 ≈ 110 KiB at (chunk, K, V) = (64, 64, 64) —
MXU-aligned matmuls throughout (the head_dim of RWKV-6 is 64; two heads
could be fused per instance to fill the 128-lane MXU, which is the
documented follow-up optimization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_call

__all__ = ["rwkv_scan_pallas"]


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref,
                 s_scratch, *, chunk: int, seq: int):
    K = r_ref.shape[-1]
    V = v_ref.shape[-1]
    n_chunks = seq // chunk

    s_scratch[...] = jnp.zeros((K, V), jnp.float32)

    def chunk_body(c, _):
        sl = pl.ds(c * chunk, chunk)
        r = r_ref[sl, :].astype(jnp.float32)
        k = k_ref[sl, :].astype(jnp.float32)
        v = v_ref[sl, :].astype(jnp.float32)
        w = w_ref[sl, :].astype(jnp.float32)
        u = u_ref[...].astype(jnp.float32)            # (K,)
        s = s_scratch[...]

        logw = jnp.log(jnp.maximum(w, 1e-30))
        P = jnp.exp(jnp.cumsum(logw, axis=0))         # inclusive (c, K)
        Pq = P / jnp.maximum(w, 1e-30)                # exclusive
        q_in = r * Pq
        y = q_in @ s                                  # (c, V)
        kP = k / jnp.maximum(P, 1e-30)
        att = q_in @ kP.T                             # (c, c)
        ti = jax.lax.iota(jnp.int32, chunk)
        tri = (ti[:, None] > ti[None, :]).astype(jnp.float32)
        att = att * tri
        diag = jnp.sum(r * u[None, :] * k, axis=1)    # (c,)
        att = att + jnp.eye(chunk, dtype=jnp.float32) * diag[:, None]
        y = y + att @ v
        Ptot = P[-1]                                  # (K,)
        # state writes use (Ptot / P_j) * k_j — kP already holds k_j / P_j
        s_new = s * Ptot[:, None] + (Ptot[None, :] * kP).T @ v
        s_scratch[...] = s_new
        y_ref[sl, :] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    s_out_ref[...] = s_scratch[...].astype(s_out_ref.dtype)


def rwkv_scan_pallas(r, k, v, w, u, *, chunk: int = 64):
    """r,k,w: (B, T, H, K); v: (B, T, H, V); u: (H, K).
    Returns (y (B, T, H, V), state (B, H, K, V)). T padded to chunk.
    Interpreted on CPU, compiled on TPU
    (:func:`repro.kernels.platform.pallas_call`)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        z = jnp.zeros((B, pad, H, K), r.dtype)
        r = jnp.concatenate([r, z], 1)
        k = jnp.concatenate([k, z], 1)
        v = jnp.concatenate([v, jnp.zeros((B, pad, H, V), v.dtype)], 1)
        w = jnp.concatenate([w, jnp.ones((B, pad, H, K), w.dtype)], 1)
    Tp = r.shape[1]

    rt = r.transpose(0, 2, 1, 3)                     # (B, H, T, K)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    wt = w.transpose(0, 2, 1, 3)

    kernel = functools.partial(_rwkv_kernel, chunk=chunk, seq=Tp)
    y, s = pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((None, None, Tp, K), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Tp, K), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Tp, V), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Tp, K), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, K), lambda b, h: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Tp, V), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, K, V), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
    )(rt, kt, vt, wt, u)
    y = y.transpose(0, 2, 1, 3)[:, :T]
    return y, s
