"""Jit'd public wrappers for the Pallas kernels.

Each kernel is interpreted on CPU and compiled on TPU, chosen by the
platform the program is lowered for (:mod:`repro.kernels.platform`). The
model code reaches these via ``impl == "pallas"`` (models/attention.py,
models/ssm.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import flash_attention_pallas
from .moe_gmm import moe_gmm_pallas
from .rwkv_scan import rwkv_scan_pallas

__all__ = ["flash_attention", "rwkv_scan", "moe_gmm"]


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None):
    return flash_attention_pallas(q, k, v, causal=causal, window=window)


@jax.jit
def rwkv_scan(r, k, v, w, u):
    return rwkv_scan_pallas(r, k, v, w, u)


@jax.jit
def moe_gmm(x, w):
    return moe_gmm_pallas(x, w)
