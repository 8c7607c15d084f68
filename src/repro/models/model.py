"""Top-level model: ``build_model(cfg) -> Model`` with init/apply/loss/
prefill/decode — the public modelling API used by the trainer, the serving
engine, and the dry-run launcher."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.specs import ShardCtx
from .layers import init_embedding, init_norm, norm_apply
from .transformer import (init_stage, init_stage_cache, no_stats,
                          stage_apply, stage_decode)

__all__ = ["Model", "build_model"]


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ---------------- params ----------------
    def init_params(self, key) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, len(cfg.stages) + 3)
        params: Dict[str, Any] = {
            "embed": init_embedding(
                keys[0], cfg.vocab_size, cfg.d_model, cfg.tie_embeddings,
                max_pos=cfg.max_seq_len if cfg.pos_embed == "learned" else 0,
                learned_pos=cfg.pos_embed == "learned", dtype=dt),
            "final_norm": init_norm(cfg.d_model, cfg.norm, dt),
            "stages": [init_stage(keys[i + 1], cfg, s)
                       for i, s in enumerate(cfg.stages)],
        }
        if cfg.encoder is not None:
            ek = jax.random.split(keys[-1], len(cfg.encoder.stages) + 1)
            params["encoder"] = {
                "stages": [init_stage(ek[i], cfg, s)
                           for i, s in enumerate(cfg.encoder.stages)],
                "final_norm": init_norm(cfg.d_model, cfg.norm, dt),
            }
        return params

    # ---------------- embedding helpers ----------------
    def _embed(self, params, tokens, ctx, offset: int = 0):
        cfg = self.cfg
        table = params["embed"]["embed"]
        # gather from a float32 view: the transpose, a scatter-add of
        # every token's gradient into its row, then accumulates in
        # float32 — in bf16 the updates to frequent tokens' rows are
        # rounded away as the row's sum grows
        x = table.astype(jnp.float32)[tokens].astype(table.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # (B, S, D)
        if cfg.pos_embed == "learned":
            S = tokens.shape[1]
            pos = params["embed"]["pos_embed"][offset:offset + S]
            x = x + pos[None]
        return ctx.res(x)

    def _logits(self, params, x, ctx):
        """Final norm and the (tied or separate) output projection, under
        the ``head`` name scope."""
        cfg = self.cfg
        with jax.named_scope("head"):
            x = norm_apply(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = x @ params["embed"]["embed"].T
            else:
                logits = x @ params["embed"]["unembed"]
            return ctx.constrain(logits, ctx.dp, None, ctx.tp)

    def _encode(self, params, frames, ctx, impl="ref"):
        """Audio encoder: frames (B, F, D) stub embeddings -> memory."""
        cfg = self.cfg
        x = frames.astype(_dtype(cfg))
        if cfg.pos_embed == "learned":
            F = x.shape[1]
            x = x + params["embed"]["pos_embed"][:F][None]
        enc_ctx = dataclasses.replace(ctx, attn_mode="qseq") \
            if ctx.mesh is not None else ctx
        for sp, s in zip(params["encoder"]["stages"], cfg.encoder.stages):
            x, _ = stage_apply(sp, x, s, enc_ctx, cfg, impl=impl)
        return norm_apply(params["encoder"]["final_norm"], x, cfg.norm_eps)

    # ---------------- forward / loss ----------------
    def apply(self, params, tokens, ctx: Optional[ShardCtx] = None, *,
              extra_embeds=None, frames=None, remat: bool = False,
              impl: str = "ref"):
        """Forward pass -> (logits, aux_loss).

        ``extra_embeds``: (B, N, D) VLM patch embeddings, prepended.
        ``frames``: (B, F, D) audio-stub embeddings for enc-dec models.
        """
        logits, stats = self._forward(params, tokens, ctx,
                                      extra_embeds=extra_embeds,
                                      frames=frames, remat=remat, impl=impl)
        return logits, stats["aux_loss"]

    def _forward(self, params, tokens, ctx, *, extra_embeds, frames, remat,
                 impl):
        """Forward pass -> (logits, stats summed over every block)."""
        cfg = self.cfg
        ctx = ctx or ShardCtx.null()
        x = self._embed(params, tokens, ctx)
        n_prefix = 0
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
            n_prefix = extra_embeds.shape[1]
            x = ctx.constrain(x, ctx.dp, None, ctx.tp)
        memory = None
        if cfg.encoder is not None:
            assert frames is not None, "enc-dec model needs frames"
            memory = self._encode(params, frames, ctx, impl=impl)
        total = no_stats()
        for sp, s in zip(params["stages"], cfg.stages):
            x, stats = stage_apply(sp, x, s, ctx, cfg, memory=memory,
                                   remat=remat, impl=impl)
            total = jax.tree.map(jnp.add, total, stats)
        if n_prefix:
            x = x[:, n_prefix:]
        return self._logits(params, x, ctx), total

    def loss(self, params, batch: dict, ctx: Optional[ShardCtx] = None, *,
             remat: bool = False, impl: str = "ref",
             example_weights=None) -> Tuple[jnp.ndarray, dict]:
        """Next-token CE (+ MoE aux + z-loss). ``example_weights`` (B,)
        realizes the m-sync participation mask (core/sync_engine). With
        experts, the metrics also hold ``moe_held_rows``: the assignments
        the held experts computed, summed over layers."""
        ctx = ctx or ShardCtx.null()
        logits, stats = self._forward(
            params, batch["tokens"], ctx,
            extra_embeds=batch.get("patch_embeds"),
            frames=batch.get("frames"), remat=remat, impl=impl)
        aux = stats["aux_loss"]
        labels = batch["labels"]                        # (B, S)
        with jax.named_scope("head"):                   # CE and z-loss
            lg = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)         # (B, S)
            gold = jnp.take_along_axis(lg, labels[..., None],
                                       axis=-1)[..., 0]
            nll = lse - gold
            w = batch.get("loss_mask")
            w = jnp.ones_like(nll) if w is None else w.astype(jnp.float32)
            if example_weights is not None:
                w = w * example_weights[:, None].astype(jnp.float32)
            denom = jnp.maximum(w.sum(), 1.0)
            ce = (nll * w).sum() / denom
            zloss = 1e-4 * ((lse ** 2) * w).sum() / denom
        total = ce + zloss + aux
        metrics = {"ce": ce, "z_loss": zloss, "aux_loss": aux}
        if self.cfg.moe is not None:
            metrics["moe_held_rows"] = stats["moe_held_rows"]
        return total, metrics

    # ---------------- serving ----------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        return {
            "stages": [init_stage_cache(cfg, s, batch, max_len)
                       for s in cfg.stages],
            "len": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params, tokens, ctx: Optional[ShardCtx] = None, *,
                frames=None, extra_embeds=None, impl: str = "ref"):
        """Prefill = full forward (the cost the dry-run measures); returns
        last-position logits."""
        logits, _ = self.apply(params, tokens, ctx, frames=frames,
                               extra_embeds=extra_embeds, impl=impl)
        return logits[:, -1]

    def decode_step(self, params, token, cache, ctx: Optional[ShardCtx]
                    = None, *, memory=None, static_cache: bool = False):
        """One decode step. token: (B, 1) int32 -> (logits (B, V), cache)."""
        cfg = self.cfg
        ctx = ctx or ShardCtx.null()
        cache_len = cache["len"]
        x = self._embed(params, token, ctx)
        if cfg.pos_embed == "learned":
            # _embed added pos[0]; shift to pos[cache_len]
            x = x - params["embed"]["pos_embed"][0][None, None] \
                + params["embed"]["pos_embed"][cache_len][None, None]
        new_stages = []
        for sp, sc, s in zip(params["stages"], cache["stages"], cfg.stages):
            x, nc = stage_decode(sp, x, sc, s, cache_len, ctx, cfg,
                                 memory=memory, static_cache=static_cache)
            new_stages.append(nc)
        logits = self._logits(params, x, ctx)[:, 0]
        new_len = cache_len if static_cache else cache_len + 1
        return logits, {"stages": new_stages, "len": new_len}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
