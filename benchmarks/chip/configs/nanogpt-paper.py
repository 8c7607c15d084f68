"""Plain float32 reference for the ``nanogpt-paper`` configuration.

A GPT-2-style decoder written from the nanoGPT description
(github.com/karpathy/nanoGPT, ``model.py``) in plain ``jax.numpy``,
importing nothing of ``repro``. Every matrix product runs at
``Precision.HIGHEST``, so on a TPU float32 is float32.

Departures from nanoGPT, each as the configuration states them:
no bias in the linear layers, the tanh form of GELU, token embeddings
scaled by ``sqrt(n_embd)``, a ``1e-4 * logsumexp**2`` z-loss beside
the cross-entropy, and weight decay on every leaf of rank 2 or more in
the stacked tree: matrices and embeddings, and also the per-layer
LayerNorm scales and biases, which nanoGPT exempts (the final LayerNorm
is exempt in both).

The parameters arrive as the benchmark made them, in the program's tree
(``embed/{embed,pos_embed}``, ``final_norm``, one stacked stage
``stages[0]['b0']`` with ``norm1``, ``mixer/{wq,wk,wv,wo}``, ``norm2``,
``ff/{w_up,w_down}``, each with a leading layer axis). They are held in
the configuration's storage type between steps, as the next step reads
them; everything else is float32.

The m-sync mask is worked out here from the configuration: under the
sqrt law ``tau_i = sqrt(i)`` the round's first ``m`` finishers are the
same every step, each group's rows weigh ``n / m`` and the rest 0.

``precision="fp8"`` is the control: every matrix product runs on float8
operands with one scale per tensor, e4m3 for the activations and
weights of the forward pass and e5m2 for the gradients of the backward
pass, accumulating in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROWS_PER_BLOCK = 4


def _round(x, dtype):
    """``x`` rounded to a float8 type under one per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_ein(spec, a, b):
    return _plain(spec, _round(a, jnp.float8_e4m3fn),
                  _round(b, jnp.float8_e4m3fn))


def _fp8_fwd(spec, a, b):
    qa, qb = _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn)
    return _plain(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(_plain, spec), *res)
    return vjp(_round(g, jnp.float8_e5m2))


_fp8_ein.defvjp(_fp8_fwd, _fp8_bwd)


def _ein(spec, a, b, precision):
    if precision == "fp8":
        return _fp8_ein(spec, a, b)
    return _plain(spec, a, b)


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits(params, tokens, cfg, precision="float32"):
    """``(B, S, V)`` float32 logits."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    p = f32(params)
    B, S = tokens.shape
    d, H = cfg["n_embd"], cfg["n_head"]
    dh, eps = d // H, cfg["norm_eps"]
    E = p["embed"]["embed"]
    x = E[tokens] * math.sqrt(d) + p["embed"]["pos_embed"][:S][None]
    causal = jnp.tril(jnp.ones((S, S), bool))
    blk = p["stages"][0]["b0"]
    for layer in range(cfg["n_layer"]):
        w = jax.tree.map(lambda a: a[layer], blk)
        h = _ln(x, w["norm1"], eps)
        q, k, v = (_ein("bsd,de->bse", h, w["mixer"][n], precision)
                   .reshape(B, S, H, dh) for n in ("wq", "wk", "wv"))
        att = _ein("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(dh)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = _ein("bhqk,bkhd->bqhd", att, v, precision).reshape(B, S, d)
        x = x + _ein("bsd,de->bse", o, w["mixer"]["wo"], precision)
        h = _ln(x, w["norm2"], eps)
        u = _gelu(_ein("bsd,df->bsf", h, w["ff"]["w_up"], precision))
        x = x + _ein("bsf,fd->bsd", u, w["ff"]["w_down"], precision)
    x = _ln(x, p["final_norm"], eps)
    return _ein("bsd,vd->bsv", x, E, precision)


def example_weights(cfg, batch: int) -> np.ndarray:
    """Per-row loss weights of one m-sync round (``batch`` rows)."""
    n, m = cfg["workers"], cfg["m"]
    taus = np.sqrt(np.arange(1, n + 1, dtype=np.float64))
    mask = np.zeros(n)
    mask[np.argsort(taus, kind="stable")[:m]] = 1.0
    return np.repeat(mask * n / m, batch // n).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _block_fn(cfg_items, precision):
    cfg = dict(cfg_items)

    def weighted_sums(params, tokens, labels, w, denom):
        lg = logits(params, tokens, cfg, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        ww = w[:, None]
        return (((lse - gold) * ww).sum()
                + 1e-4 * ((lse ** 2) * ww).sum()) / denom

    return jax.jit(jax.value_and_grad(weighted_sums))


def loss_and_grads(params, tokens, labels, weights, cfg,
                   precision="float32"):
    """Loss and float32 gradients, in blocks of rows."""
    fn = _block_fn(tuple(sorted((k, v) for k, v in cfg.items()
                                if not isinstance(v, (dict, list)))),
                   precision)
    B, S = tokens.shape
    denom = jnp.float32(max(float(np.sum(weights)) * S, 1.0))
    loss, grads = 0.0, None
    for r in range(0, B, ROWS_PER_BLOCK):
        sl = slice(r, r + ROWS_PER_BLOCK)
        val, g = fn(params, jnp.asarray(tokens[sl]),
                    jnp.asarray(labels[sl]), jnp.asarray(weights[sl]), denom)
        loss += float(val)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return loss, jax.tree.map(lambda a: a.astype(jnp.float32), grads)


def lr_at(step: int, opt: dict) -> float:
    base, warm, total = opt["lr"], opt["warmup"], opt["schedule_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (opt["min_ratio"] + (1 - opt["min_ratio"])
                   * 0.5 * (1 + math.cos(math.pi * frac)))


def adamw(p, m, v, g, step: int, opt: dict):
    """One AdamW step on trees: ``g`` already clipped; returns the new
    parameters (in their storage type), ``m`` and ``v``."""
    t, lr = step + 1, lr_at(step, opt)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

    def upd(pp, mm, vv):
        u = (mm / (1 - b1 ** t)) / (jnp.sqrt(vv / (1 - b2 ** t)) + eps)
        pf = pp.astype(jnp.float32)
        if pp.ndim >= 2:
            u = u + wd * pf
        return (pf - lr * u).astype(pp.dtype)

    return jax.tree.map(upd, p, m, v), m, v


def clip(g, max_norm: float):
    gn = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(g)))
    scale = min(1.0, max_norm / max(gn, 1e-9))
    return jax.tree.map(lambda x: x * scale, g)


def train_steps(params, batches, cfg, steps: int = 3,
                precision="float32") -> dict:
    """``steps`` AdamW steps from ``params`` (host arrays in the storage
    type) over ``batches`` (``[(tokens, labels)]``): each step's loss,
    the first step's gradient as AdamW receives it (clipped), and the
    parameters after the last step."""
    opt = cfg["optimizer"]
    p = jax.tree.map(jnp.asarray, params)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    losses, first_grad = [], None
    for step in range(steps):
        tokens, labels = batches[step]
        w = example_weights(cfg, tokens.shape[0])
        loss, g = loss_and_grads(p, tokens, labels, w, cfg, precision)
        losses.append(loss)
        g = clip(g, opt["clip_norm"])
        if first_grad is None:
            first_grad = g
        p, m, v = adamw(p, m, v, g, step, opt)
    return {"losses": losses, "first_grad": first_grad, "params": p}
