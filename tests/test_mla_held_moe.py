"""DeepSeek-V2-Lite's parts at a small size on seeded random weights:
latent attention and YaRN against direct transcriptions of the
equations, the held-expert layer against the uncut layer it is a share
of, dropless routing under forced imbalance, and the configuration
through the launcher."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, Stage, Block
from repro.models.mla import (causal_attention, init_mla, mla_apply,
                              softmax_scale, yarn_inv_freq)
from repro.models.moe import init_moe, moe_forward
from repro.sharding.specs import ShardCtx

DSV2 = get_config("deepseek-v2-lite-ep8")


def test_yarn_frequencies_and_scale_of_deepseek_v2_lite():
    # the published rope_scaling: factor 40, original 4096, beta 32 / 1,
    # mscale = mscale_all_dim = 0.707, rope head 64, theta 10000
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    d = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(1e4))
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(yarn_inv_freq(DSV2.mla), want, rtol=1e-12)
    assert softmax_scale(DSV2.mla) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192))


def _small_mla_cfg(S):
    mla = MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                    v_head_dim=6, rope_factor=40.0, original_max_position=8,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707)
    return dataclasses.replace(
        reduced(DSV2, d_model=32, vocab=64), dtype="float32", mla=mla,
        attn=dataclasses.replace(DSV2.attn, num_heads=3))


def _transcribed_mla(p, x, cfg):
    """Per token and head, straight from the equations, in NumPy."""
    a, H = cfg.mla, cfg.attn.num_heads
    nope, rope, r, dv = (a.qk_nope_head_dim, a.qk_rope_head_dim,
                         a.kv_lora_rank, a.v_head_dim)
    p = jax.tree.map(lambda t: np.asarray(t, np.float64), p)
    x = np.asarray(x, np.float64)
    B, S, _ = x.shape
    inv = yarn_inv_freq(a)
    out = np.zeros_like(x)
    for b in range(B):
        q = (x[b] @ p["wq"]).reshape(S, H, nope + rope)
        kva = x[b] @ p["wkv_a"]
        c = kva[:, :r] / np.sqrt((kva[:, :r] ** 2).mean(-1, keepdims=True)
                                 + cfg.norm_eps) * p["kv_norm"]["scale"]
        kv = (c @ p["wkv_b"]).reshape(S, H, nope + dv)

        def rot(v, t):
            h = rope // 2
            cs, sn = np.cos(t * inv), np.sin(t * inv)
            return np.concatenate([v[:h] * cs - v[h:] * sn,
                                   v[h:] * cs + v[:h] * sn])

        o = np.zeros((S, H, dv))
        for h in range(H):
            for t in range(S):
                qt = np.concatenate([q[t, h, :nope], rot(q[t, h, nope:], t)])
                s = np.array([qt @ np.concatenate(
                    [kv[u, h, :nope], rot(kva[u, r:], u)])
                    for u in range(t + 1)]) * softmax_scale(a)
                w = np.exp(s - s.max())
                w /= w.sum()
                o[t, h] = w @ kv[:t + 1, h, nope:]
        out[b] = o.reshape(S, H * dv) @ p["wo"]
    return out


def test_mla_matches_a_transcription_of_the_equations():
    S = 12
    cfg = _small_mla_cfg(S)
    p = init_mla(jax.random.key(0), 32, 3, cfg.mla)
    p["kv_norm"]["scale"] = 1 + 0.1 * jax.random.normal(jax.random.key(2),
                                                          (16,))
    x = jax.random.normal(jax.random.key(1), (2, S, 32))
    got = jax.jit(lambda p, x: mla_apply(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(got), _transcribed_mla(p, x, cfg),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block", [2, 5, 16])
def test_attention_in_query_blocks_is_the_whole_causal_softmax(block):
    ks = jax.random.split(jax.random.key(3), 3)
    q, k = (jax.random.normal(kk, (2, 11, 3, 5)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (2, 11, 3, 4))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    s = jnp.where(jnp.tril(jnp.ones((11, 11), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    got = jax.jit(lambda *a: causal_attention(*a, 0.3, block=block))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # and so are its gradients, each block recomputed
    f = lambda *a: (causal_attention(*a, 0.3, block=block) ** 2).sum()
    g = lambda *a: (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((11, 11), bool)),
                  jnp.einsum("bqhd,bkhd->bhqk", a[0], a[1]) * 0.3,
                  -jnp.inf), -1), a[2]) ** 2).sum()
    for a, b in zip(jax.jit(jax.grad(f, (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(g, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


E, K, D, F = 8, 3, 16, 12


def _moe_cfg(**moe_kw):
    moe = MoEConfig(num_experts=E, experts_per_token=K, d_expert=F,
                    num_shared_experts=1, d_shared=F, norm_topk_prob=False,
                    router_aux_weight=1e-3, **moe_kw)
    return ModelConfig(name="t", arch_type="moe", d_model=D, vocab_size=8,
                       d_ff=F, stages=(Stage((Block("attn", "moe"),)),),
                       attn=DSV2.attn, moe=moe, dtype="float32")


def _swiglu(w, t):
    return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]


def _uncut(p, x):
    """The whole layer: shared expert plus sum over the top k of the
    softmax score times the expert, every expert dense."""
    s = jax.nn.softmax(x @ p["router"], -1)
    top = jax.lax.top_k(s, K)[1]
    sw = _swiglu
    y = sw(p["shared"], x)
    for e in range(E):
        g = jnp.where((top == e).any(-1), s[:, e], 0.0)
        y = y + g[:, None] * sw({"w_gate": p["expert_gate"][e],
                                 "w_up": p["expert_up"][e],
                                 "w_down": p["expert_down"][e]}, x)
    return y


def _forward(p, x, cfg):
    return jax.jit(lambda p, x: moe_forward(p, x, ShardCtx.null(), cfg))(p, x)


def _share(p, lo, n):
    return {**p, **{k: p[k][lo:lo + n] for k in
                    ("expert_up", "expert_gate", "expert_down")}}


def test_the_shares_add_up_to_the_uncut_layer():
    whole = _moe_cfg(experts_held=E)
    p = init_moe(jax.random.key(0), D, whole.moe, "swiglu")
    x = jax.random.normal(jax.random.key(1), (2, 24, D))
    want = jax.jit(_uncut)(p, x.reshape(-1, D)).reshape(x.shape)
    got, rows = 0.0, 0
    for lo in range(0, E, 4):            # 2 chips, 4 experts each
        cfg = _moe_cfg(experts_held=4, held_offset=lo)
        out, stats = _forward(_share(p, lo, 4), x, cfg)
        got = got + out
        rows += int(stats["moe_held_rows"])
    got = got - _swiglu(p["shared"], x)   # each share adds it
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert rows == 2 * 24 * K            # every assignment, once
    one, stats = _forward(p, x, whole)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert int(stats["moe_held_rows"]) == 2 * 24 * K


def test_dropless_when_every_token_picks_one_held_expert():
    cfg = _moe_cfg(experts_held=2)
    p = init_moe(jax.random.key(4), D, cfg.moe, "swiglu")
    x = jnp.abs(jax.random.normal(jax.random.key(5), (1, 64, D))) + 0.5
    # experts 0 and 1 (held) score far above the rest for every token
    p["router"] = p["router"].at[:, 0].set(3.0).at[:, 1].set(2.0)
    out, stats = _forward(p, x, cfg)
    assert int(stats["moe_held_rows"]) == 2 * 64     # none dropped
    full = {**p, **{k: jnp.concatenate(
        [p[k], jnp.zeros((E - 2,) + p[k].shape[1:])]) for k in
        ("expert_up", "expert_gate", "expert_down")}}
    want = jax.jit(_uncut)(full, x.reshape(-1, D)).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # the capacity path of the same layer would have dropped most rows
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=0, capacity_factor=1.0))
    _, st = _forward(full, x, capped)
    assert int(st["moe_held_rows"]) < K * 64


def test_held_expert_layer_gradients_are_finite_and_flow_to_the_router():
    cfg = _moe_cfg(experts_held=2, held_offset=2)
    p = init_moe(jax.random.key(6), D, cfg.moe, "swiglu")
    x = jax.random.normal(jax.random.key(7), (2, 16, D))

    def loss(p):
        out, st = moe_forward(p, x, ShardCtx.null(), cfg)
        return (out ** 2).sum() + st["aux_loss"]

    g = jax.jit(jax.grad(loss))(p)
    assert all(np.all(np.isfinite(np.asarray(a))) for a in jax.tree.leaves(g))
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["expert_up"]).sum()) > 0


def test_deepseek_v2_lite_builds_through_the_launcher_as_stated():
    from repro.launch.train import build_run

    cfg, trainer, _ = build_run("deepseek-v2-lite-ep8", steps=20, batch=8,
                                seq=64, policy="m_sync", m=6, workers=8,
                                time_model="sqrt")
    assert cfg is DSV2 and trainer.remat
    assert (cfg.d_model, cfg.attn.num_heads, cfg.d_ff) == (2048, 16, 10944)
    a, m = cfg.mla, cfg.moe
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
            a.v_head_dim) == (512, 128, 64, 128)
    assert (m.num_experts, m.experts_per_token, m.d_expert,
            m.num_shared_experts, m.held) == (64, 6, 1408, 2, 8)
    assert not m.norm_topk_prob and not cfg.embed_scale
    assert cfg.num_layers == 6 and cfg.vocab_size == 12800
    # the held experts are what is counted: ~635M on the chip
    assert cfg.param_count() == pytest.approx(635.46e6, rel=1e-4)
    held = 3 * 2048 * 1408
    assert cfg.param_count() - cfg.active_param_count() == pytest.approx(
        5 * (8 - 6 * 8 / 64) * held, rel=1e-6)
    shapes = jax.eval_shape(trainer.model.init_params, jax.random.key(0))
    ff = shapes["stages"][1]["b0"]["ff"]
    assert ff["expert_up"].shape == (5, 8, 2048, 1408)
    assert ff["router"].shape == (5, 2048, 64)


def test_the_launcher_cuts_no_width_unless_asked():
    from repro.launch.train import build_run

    cfg, _, _ = build_run("llama3.2-3b", batch=2, seq=16)
    assert cfg.d_model == 3072 and cfg.vocab_size == 128256
    cfg, _, _ = build_run("llama3.2-3b", batch=2, seq=16, reduced=True)
    assert cfg.d_model == 256 and cfg.vocab_size == 2048


def test_embedding_scale_is_the_configurations():
    from repro.models import build_model

    nano = reduced(get_config("nanogpt-paper"), d_model=64, vocab=32)
    plain = dataclasses.replace(nano, embed_scale=False)
    assert nano.embed_scale and not DSV2.embed_scale
    p = jax.jit(build_model(nano).init_params)(jax.random.key(0))
    toks = jnp.arange(8)[None]
    a = build_model(nano)._embed(p, toks, ShardCtx.null())
    b = build_model(plain)._embed(p, toks, ShardCtx.null())
    pos = p["embed"]["pos_embed"][:8][None]
    np.testing.assert_allclose(np.asarray(a - pos),
                               8.0 * np.asarray(b - pos), rtol=1e-5,
                               atol=1e-6)
