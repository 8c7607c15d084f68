"""Mixture-of-Experts with token-choice top-k routing and capacity.

TPU-native design (DESIGN.md §4): experts are sharded over the ``model``
mesh axis via ``shard_map``; tokens stay local to their data shard and are
*replicated* across the model axis, so the dispatch (argsort + gather +
scatter) is entirely local — the only collective is one psum combining the
per-shard expert outputs. This avoids the (tokens × experts × capacity)
dense dispatch tensor (intractable at Kimi-K2 scale) and avoids sorting a
sharded axis (collective-heavy under GSPMD).

Routing: softmax router, top-k experts per token, per-expert capacity
``C = ceil(T_local * k / E_global * capacity_factor)``; overflow tokens are
dropped (token-choice with capacity, as in DeepSeekMoE/Switch). Shared
experts (DeepSeekMoE) run as a dense SwiGLU on every token, hidden sharded
over ``model``. Aux load-balance loss follows Switch: ``E * Σ_e f_e · p_e``.

Held experts (``MoEConfig.experts_held``): the layer is one chip's share of
an expert-parallel deployment. It routes every token over all
``num_experts``, drops nothing, and computes only the experts it holds:
their assignments are sorted by expert and run through
``jax.lax.ragged_dot`` with each held expert's row count as its group
size. What the absent experts would add is left out; with one chip no
exchange runs. Named scopes: ``moe_route`` (router, top-k, sort, gather),
``moe_experts`` (the grouped products), ``moe_combine``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init

__all__ = ["init_moe", "moe_apply", "moe_forward"]


def init_moe(key, d: int, cfg_moe, act: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    E, fe = cfg_moe.held, cfg_moe.d_expert
    p = {
        "router": dense_init(ks[0], (d, cfg_moe.num_experts),
                             dtype=jnp.float32),
        "expert_up": _expert_init(ks[1], E, d, fe, dtype),
        "expert_down": _expert_init(ks[2], E, fe, d, dtype),
    }
    if act == "swiglu":
        p["expert_gate"] = _expert_init(ks[3], E, d, fe, dtype)
    if cfg_moe.num_shared_experts:
        fs = cfg_moe.d_shared * cfg_moe.num_shared_experts
        p["shared"] = {
            "w_up": dense_init(ks[4], (d, fs), dtype=dtype),
            "w_down": dense_init(ks[5], (fs, d), dtype=dtype),
        }
        if act == "swiglu":
            p["shared"]["w_gate"] = dense_init(
                jax.random.fold_in(ks[4], 1), (d, fs), dtype=dtype)
    return p


def _expert_init(key, E: int, din: int, dout: int, dtype):
    keys = jax.random.split(key, E)
    return jax.vmap(lambda k: dense_init(k, (din, dout), dtype=dtype))(keys)


def _local_moe(x, router_w, gate_w, up_w, down_w, *, k: int, E: int,
               capacity: int, act: str, model_size: int,
               model_axis: Optional[str], shard_idx,
               scatter_output: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-device MoE. x: (T, D) local tokens (replicated over model axis);
    expert weights: (E_local, ...) — this shard's slice. Returns
    (out (T, D) partial — needs psum over model, aux_loss scalar)."""
    T, D = x.shape
    E_local = up_w.shape[0]
    lo = shard_idx * E_local

    topw, topi, aux = _route(x, router_w, k, E, renormalize=True)

    # ---- local dispatch: keep only assignments to this shard's experts
    flat_e = topi.reshape(T * k)                           # global expert ids
    flat_w = topw.reshape(T * k)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    local_e = flat_e - lo
    is_local = (local_e >= 0) & (local_e < E_local)
    sort_key = jnp.where(is_local, local_e, E_local)       # non-local last
    order = jnp.argsort(sort_key)
    e_sorted = sort_key[order]
    tok_sorted = flat_tok[order]
    w_sorted = jnp.where(is_local[order], flat_w[order], 0.0)

    # position of each assignment within its expert group
    group_start = jnp.searchsorted(e_sorted, jnp.arange(E_local + 1),
                                   side="left")
    pos = jnp.arange(T * k) - group_start[e_sorted]
    keep = (e_sorted < E_local) & (pos < capacity)
    slot = jnp.where(keep, e_sorted * capacity + pos, E_local * capacity)

    # gather tokens -> expert buffers (E_local, C, D); dropped -> dummy row
    xb = x[tok_sorted]                                     # (T*k, D)
    buf = jnp.zeros((E_local * capacity + 1, D), x.dtype).at[slot].set(
        xb, mode="drop")
    buf = buf[:-1].reshape(E_local, capacity, D)

    # ---- expert FFN (grouped matmul; this is the kernels/moe_gmm target)
    h = jnp.einsum("ecd,edf->ecf", buf, up_w)
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate_w)) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, down_w)              # (E_local, C, D)

    # ---- combine: weighted scatter-add back to tokens
    y_flat = y.reshape(E_local * capacity, D)
    contrib = jnp.where(keep[:, None], y_flat[jnp.minimum(slot, E_local * capacity - 1)]
                        * w_sorted[:, None].astype(y.dtype), 0.0)
    out = jnp.zeros((T, D), y.dtype).at[tok_sorted].add(contrib)
    rows = keep.sum()

    if model_axis is not None:
        rows = jax.lax.psum(rows, model_axis)
        if scatter_output:
            # reduce-scatter into the d-sharded residual stream: each model
            # shard keeps its D/ms slice — half the ICI bytes of the
            # all-reduce whose result would immediately be re-sliced anyway
            out = jax.lax.psum_scatter(out, model_axis, scatter_dimension=1,
                                       tiled=True)
        else:
            out = jax.lax.psum(out, model_axis)
    return out, aux, rows


def _route(x, router_w, k: int, E: int, renormalize: bool):
    """Softmax router over all ``E`` experts in float32, greedy top-k.
    Returns the top-k weights (renormalised to sum to 1 if asked, else
    the scores), their expert ids, and the Switch aux loss."""
    logits = jnp.dot(x.astype(jnp.float32), router_w,
                     precision=jax.lax.Precision.HIGHEST)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                   # (T, k)
    if renormalize:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss (computed identically on every shard — replicated):
    # f_e = fraction of tokens routed to e (top-1..k), p_e = mean prob.
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)    # (T, k, E)
    f = onehot.sum(axis=(0, 1)) / (topi.shape[0] * k)
    pbar = probs.mean(axis=0)
    return topw, topi, E * jnp.sum(f * pbar)


@jax.custom_vjp
def _take_rows(x, idx, inv):
    """``x[idx]`` where ``idx`` maps the rows of the result onto the rows
    of ``x`` and ``inv[r]`` lists, in ``(rows of x, n)`` shape, the ``n``
    rows of the result that read row ``r``. Its transpose gathers with
    ``inv`` and sums: no scatter."""
    return x[idx]


def _take_rows_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _take_rows_bwd(res, g):
    idx, inv = res
    return g[inv].sum(1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _held_moe(x, p: dict, m, act: str):
    """Dropless routed part of the held experts. x: (T, D) -> (out (T, D),
    aux, rows): ``rows`` counts the assignments the held experts computed.
    Every tensor is sized by T * k, and the grouped products run over the
    held rows only."""
    T, D = x.shape
    k, H = m.experts_per_token, m.held
    with jax.named_scope("moe_route"):
        topw, topi, aux = _route(x, p["router"], k, m.num_experts,
                                 m.norm_topk_prob)
        e = topi.reshape(T * k) - m.held_offset
        held = (e >= 0) & (e < H)
        key = jnp.where(held, e, H)                        # absent last
        order = jnp.argsort(key, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype), unique_indices=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(H)[None, :], axis=0,
                        dtype=jnp.int32)                   # (H,)
        # rows past the held ones are not computed by the grouped
        # products, and what those leave there is never read
        computed = (jnp.arange(T * k) < sizes.sum())[:, None]
        xs = jnp.where(computed,
                       _take_rows(x, order // k, inv.reshape(T, k)), 0)
    with jax.named_scope("moe_experts"):
        h = jax.lax.ragged_dot(xs, p["expert_up"], sizes)
        if act == "swiglu":
            h = jax.nn.silu(jax.lax.ragged_dot(xs, p["expert_gate"],
                                               sizes)) * h
        else:
            h = jax.nn.gelu(h)
        ys = jax.lax.ragged_dot(h, p["expert_down"], sizes)
    with jax.named_scope("moe_combine"):
        ys = jnp.where(computed, ys, 0)
        y = _take_rows(ys, inv, order[:, None]).reshape(T, k, D)
        w = jnp.where(held, topw.reshape(T * k), 0.0).reshape(T, k)
        out = jnp.einsum("tk,tkd->td", w.astype(y.dtype), y,
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), aux, sizes.sum()


def moe_apply(p: dict, x: jnp.ndarray, ctx, cfg,
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    out, stats = moe_forward(p, x, ctx, cfg)
    return out, stats["aux_loss"]


def moe_forward(p: dict, x: jnp.ndarray, ctx, cfg):
    """x: (B, S, D) -> (out (B, S, D), stats): ``stats["aux_loss"]`` the
    weighted aux loss, ``stats["moe_held_rows"]`` the assignments the
    layer's experts computed (int32)."""
    m = cfg.moe
    B, S, D = x.shape
    E = m.num_experts
    ms = max(ctx.model_size, 1)
    assert E % ms == 0, f"{E} experts not divisible by model={ms}"
    act = cfg.mlp_act

    if m.experts_held:
        if ms > 1:
            raise NotImplementedError(
                "a layer of held experts is one chip's share; it runs "
                "without a model axis")
        out, aux, rows = _held_moe(x.reshape(B * S, D), p, m, act)
        out = out.reshape(B, S, D)
    elif ctx.mesh is None or ms == 1:
        T = B * S
        capacity = _capacity(T, m.experts_per_token, E, m.capacity_factor)
        out, aux, rows = _local_moe(
            x.reshape(T, D), p["router"], p.get("expert_gate"),
            p["expert_up"], p["expert_down"], k=m.experts_per_token, E=E,
            capacity=capacity, act=act, model_size=1, model_axis=None,
            shard_idx=0)
        out = out.reshape(B, S, D)
    else:
        dp_axes = ctx.dp_axes if ctx.shard_batch else ()
        dp_total = 1
        for a in dp_axes:
            dp_total *= ctx.mesh.shape[a]
        T_local = (B // dp_total) * S
        capacity = _capacity(T_local, m.experts_per_token, E,
                             m.capacity_factor)
        dp_spec = None if not dp_axes else (
            dp_axes if len(dp_axes) > 1 else dp_axes[0])
        model_axis = ctx.model_axis

        # 2D expert-weight sharding (kimi-scale): weights additionally
        # sharded over the dp axes for STORAGE (FSDP/ZeRO-3-style) and
        # gathered per layer before use. Per-device storage drops by |dp|.
        two_d = m.shard_experts_2d and bool(ctx.dp_axes)
        w_dp = ((ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0])
                if two_d else None)

        scatter = D % ms == 0

        def mapped(xl, rw, gw, uw, dw):
            xl2 = xl.reshape(-1, D)
            idx = jax.lax.axis_index(model_axis)
            if two_d:
                uw = jax.lax.all_gather(uw, ctx.dp_axes, axis=2, tiled=True)
                dw = jax.lax.all_gather(dw, ctx.dp_axes, axis=1, tiled=True)
                if gw.ndim:
                    gw = jax.lax.all_gather(gw, ctx.dp_axes, axis=2,
                                            tiled=True)
            out, aux, rows = _local_moe(
                xl2, rw, gw, uw, dw, k=m.experts_per_token, E=E,
                capacity=capacity, act=act, model_size=ms,
                model_axis=model_axis, shard_idx=idx,
                scatter_output=scatter)
            # aux is identical across model shards (same tokens/router);
            # average across data shards so the P() out-spec is truthful.
            if dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)
                rows = jax.lax.psum(rows, dp_axes)
            out_shape = xl.shape if not scatter else \
                (xl.shape[0], xl.shape[1], xl.shape[2] // ms)
            return out.reshape(out_shape), aux, rows

        up_spec = P(model_axis, None, w_dp)
        out_spec = P(dp_spec, None, model_axis) if scatter \
            else P(dp_spec, None, None)
        out, aux, rows = jax.shard_map(
            mapped, mesh=ctx.mesh,
            in_specs=(P(dp_spec, None, None), P(None, None),
                      up_spec if "expert_gate" in p else P(),
                      up_spec, P(model_axis, w_dp, None)),
            out_specs=(out_spec, P(), P()),
            check_vma=False,
        )(x, p["router"], p.get("expert_gate", jnp.zeros((), x.dtype)),
          p["expert_up"], p["expert_down"])

    if "shared" in p:
        sh = p["shared"]
        h = x @ sh["w_up"]
        if act == "swiglu":
            h = jax.nn.silu(x @ sh["w_gate"]) * h
        else:
            h = jax.nn.gelu(h)
        h = ctx.constrain(h, ctx.dp, None, ctx.tp)
        out = out + h @ sh["w_down"]
    return out, {"aux_loss": aux * m.router_aux_weight,
                 "moe_held_rows": rows.astype(jnp.int32)}


def _capacity(T_local: int, k: int, E: int, factor: float) -> int:
    c = int(math.ceil(T_local * k / E * factor))
    return max(8, min(c, T_local))
