"""Grouped matmul (GMM) Pallas kernel — the MoE expert-FFN hot loop.

Computes ``out[e] = x[e] @ w[e]`` for ``E`` expert buffers of shape
(capacity, din) against per-expert weights (din, dout), i.e. the
``einsum("ecd,edf->ecf")`` at the heart of models/moe.py.

Grid: (E, capacity/block_m, dout/block_n); the contraction dim din is
streamed through VMEM in block_k slices with a float32 accumulator in
scratch. Blocks default to 128x128x128 (MXU-aligned); VMEM per instance =
(block_m + block_n) * block_k + block_m * block_n floats ≈ 190 KiB.

A production variant would take ragged ``group_sizes`` (dropless MoE) and
skip empty tiles via scalar prefetch; with fixed capacity the dense grid
is already the exact cost model the dry-run measures.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_call

__all__ = ["moe_gmm_pallas"]


def _gmm_kernel(x_ref, w_ref, o_ref, acc, *, n_k: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                        w_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _flush():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def moe_gmm_pallas(x, w, *, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128):
    """x: (E, C, din); w: (E, din, dout) -> (E, C, dout). Interpreted on
    CPU, compiled on TPU (:func:`repro.kernels.platform.pallas_call`)."""
    E, C, din = x.shape
    _, _, dout = w.shape
    block_m = min(block_m, C)
    block_n = min(block_n, dout)
    block_k = min(block_k, din)
    pad_m = (-C) % block_m
    pad_n = (-dout) % block_n
    pad_k = (-din) % block_k
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_k), (0, pad_n)))
    Cp, dinp, doutp = x.shape[1], x.shape[2], w.shape[2]
    n_k = dinp // block_k

    out = pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid=(E, Cp // block_m, doutp // block_n, n_k),
        in_specs=[
            pl.BlockSpec((None, block_m, block_k),
                         lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((None, block_k, block_n),
                         lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((None, block_m, block_n),
                               lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, doutp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )(x, w)
    return out[:, :C, :dout]
