"""Block/stage composition: scan-over-layers, remat, enc-dec, decode.

Every stage's repeated pattern is stacked (leading ``repeats`` axis on all
leaves) and executed under ``lax.scan`` — the lowered HLO contains one copy
of the pattern regardless of depth, which is what makes the 61-/88-layer
dry-runs compile on a single CPU host. Training bodies are wrapped in
``jax.checkpoint`` (per-layer remat).

Residual-stream activations between blocks carry the sharding constraint
``P(dp, None, model)`` so scan-carried values never replicate d_model.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import Block, ModelConfig, Stage
from .attention import (attn_apply, decode_attn_apply, init_attn,
                        init_kv_cache)
from .layers import init_mlp, init_norm, mlp_apply, norm_apply
from .mla import init_mla, mla_apply
from .moe import init_moe, moe_apply, moe_forward
from .ssm import (init_mamba, init_mamba_state, init_rwkv, init_rwkv_state,
                  mamba_apply, mamba_decode, rwkv_apply, rwkv_decode)

__all__ = ["init_block", "init_stage", "stage_apply", "stage_decode",
           "init_stage_cache"]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_block(key, cfg: ModelConfig, block: Block) -> dict:
    dt = _dtype(cfg)
    d = cfg.d_model
    a = cfg.attn
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(d, cfg.norm, dt)}
    if block.mixer in ("attn", "cross"):
        p["mixer"] = init_attn(ks[0], d, a.num_heads, a.num_kv_heads,
                               a.head_dim, dtype=dt)
    elif block.mixer == "mla":
        p["mixer"] = init_mla(ks[0], d, a.num_heads, cfg.mla, dtype=dt)
    elif block.mixer == "mamba":
        p["mixer"] = init_mamba(ks[0], d, cfg.ssm, dtype=dt)
    elif block.mixer == "rwkv":
        p["mixer"] = init_rwkv(ks[0], d, cfg.ssm.head_dim, dtype=dt)
    else:
        raise ValueError(block.mixer)
    if block.ff != "none":
        p["norm2"] = init_norm(d, cfg.norm, dt)
        if block.ff == "mlp":
            p["ff"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_act, dtype=dt)
        else:
            p["ff"] = init_moe(ks[1], d, cfg.moe, cfg.mlp_act, dtype=dt)
    return p


def init_stage(key, cfg: ModelConfig, stage: Stage) -> dict:
    """Stacked params: every leaf gets a leading (repeats,) axis."""
    def init_unit(k):
        ks = jax.random.split(k, len(stage.pattern))
        return {f"b{i}": init_block(ks[i], cfg, b)
                for i, b in enumerate(stage.pattern)}

    keys = jax.random.split(key, stage.repeats)
    return jax.vmap(init_unit)(keys)


def no_stats() -> dict:
    """What a block adds to the forward pass's sums besides its output:
    the weighted MoE aux loss and the assignments its held experts
    computed."""
    return {"aux_loss": jnp.zeros((), jnp.float32),
            "moe_held_rows": jnp.zeros((), jnp.int32)}


def _block_apply(p: dict, x, block: Block, ctx, cfg, *, memory=None,
                 impl: str = "ref"):
    """One block forward (train/prefill). Returns (x, stats)."""
    stats = no_stats()
    h = norm_apply(p["norm1"], x, cfg.norm_eps)
    if block.mixer == "attn":
        h = attn_apply(p["mixer"], h, ctx, cfg, causal=cfg.attn.causal,
                       impl=impl)
    elif block.mixer == "mla":
        h = mla_apply(p["mixer"], h, cfg)
    elif block.mixer == "cross":
        h = attn_apply(p["mixer"], h, ctx, cfg, kv_x=memory, causal=False,
                       impl=impl)
    elif block.mixer == "mamba":
        h = mamba_apply(p["mixer"], h, ctx, cfg)
    elif block.mixer == "rwkv":
        h = rwkv_apply(p["mixer"], h, ctx, cfg, impl=impl)
    x = x + h
    x = ctx.res(x)
    if block.ff != "none":
        h = norm_apply(p["norm2"], x, cfg.norm_eps)
        if block.ff == "mlp":
            h = mlp_apply(p["ff"], h, cfg.mlp_act, ctx)
        else:
            h, stats = moe_forward(p["ff"], h, ctx, cfg)
        x = x + h
        x = ctx.res(x)
    return x, stats


def _add(a: dict, b: dict) -> dict:
    return jax.tree.map(jnp.add, a, b)


def stage_apply(params: dict, x, stage: Stage, ctx, cfg, *, memory=None,
                remat: bool = False, impl: str = "ref"):
    """Forward through a stage. Returns (x, stats summed over its blocks;
    see :func:`no_stats`)."""

    def unit(x, unit_params):
        total = no_stats()
        for i, b in enumerate(stage.pattern):
            x, stats = _block_apply(unit_params[f"b{i}"], x, b, ctx, cfg,
                                    memory=memory, impl=impl)
            total = _add(total, stats)
        return x, total

    if remat:
        unit = jax.checkpoint(unit)

    def body(carry, unit_params):
        x, total = carry
        x, stats = unit(x, unit_params)
        return (x, _add(total, stats)), None

    (x, total), _ = jax.lax.scan(body, (x, no_stats()), params)
    return x, total


# ---------------------------------------------------------------------------
# Decode (single token, stacked caches threaded through the scan)
# ---------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, block: Block, batch: int,
                     max_len: int) -> Optional[dict]:
    dt = _dtype(cfg)
    a = cfg.attn
    if block.mixer == "attn":
        return init_kv_cache(batch, max_len, a.num_kv_heads, a.head_dim, dt)
    if block.mixer == "cross":
        # cross-attention reads the (static) encoder memory — no cache
        return {}
    if block.mixer == "mamba":
        return init_mamba_state(batch, cfg.d_model, cfg.ssm, dt)
    if block.mixer == "rwkv":
        return init_rwkv_state(batch, cfg.d_model, cfg.ssm.head_dim, dt)
    raise ValueError(block.mixer)


def init_stage_cache(cfg: ModelConfig, stage: Stage, batch: int,
                     max_len: int) -> dict:
    """Stacked (repeats, ...) caches matching init_stage's layout."""
    def one(_):
        return {f"b{i}": init_block_cache(cfg, b, batch, max_len)
                for i, b in enumerate(stage.pattern)}

    return jax.vmap(one)(jnp.arange(stage.repeats))


def _block_decode(p: dict, x, cache, block: Block, cache_len, ctx, cfg, *,
                  memory=None, static_cache: bool = False):
    h = norm_apply(p["norm1"], x, cfg.norm_eps)
    if block.mixer == "attn":
        h, cache = decode_attn_apply(p["mixer"], h, cache, cache_len, ctx,
                                     cfg, static_cache=static_cache)
    elif block.mixer == "cross":
        h = attn_apply(p["mixer"], h, ctx, cfg, kv_x=memory, causal=False)
    elif block.mixer == "mamba":
        h, cache = mamba_decode(p["mixer"], h, cache, ctx, cfg)
    elif block.mixer == "rwkv":
        h, cache = rwkv_decode(p["mixer"], h, cache, ctx, cfg)
    x = x + h
    if block.ff != "none":
        h = norm_apply(p["norm2"], x, cfg.norm_eps)
        if block.ff == "mlp":
            h = mlp_apply(p["ff"], h, cfg.mlp_act, ctx)
        else:
            h, _ = moe_apply(p["ff"], h, ctx, cfg)
        x = x + h
    x = ctx.constrain(x, ctx.dp, None, ctx.tp)
    return x, cache


def stage_decode(params: dict, x, caches: dict, stage: Stage, cache_len,
                 ctx, cfg, *, memory=None, static_cache: bool = False):
    """One-token decode through a stage. Returns (x, new_caches)."""

    def body(x, scanned):
        unit_params, unit_cache = scanned
        new_cache = {}
        for i, b in enumerate(stage.pattern):
            x, c = _block_decode(unit_params[f"b{i}"], x,
                                 unit_cache[f"b{i}"], b, cache_len, ctx,
                                 cfg, memory=memory,
                                 static_cache=static_cache)
            new_cache[f"b{i}"] = c
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches
