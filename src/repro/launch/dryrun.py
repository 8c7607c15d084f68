import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# 512 placeholder host devices by design: the dry-run never takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

For each combination this builds the production mesh (single-pod 16x16 or
multi-pod 2x16x16 over 512 placeholder host devices), the real train/serve
step with full sharding, then ``jit(...).lower(<ShapeDtypeStructs>)
.compile()`` — no arrays are ever allocated. The compiled artifact yields
``memory_analysis()`` (fits-in-HBM proof) and ``cost_analysis()`` +
parsed collective bytes (the §Roofline inputs). Results are cached as JSON
under ``experiments/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import INPUT_SHAPES, get_config
from ..configs.base import InputShape, ModelConfig
from ..models import build_model
from ..optim import sgd
from ..sharding.specs import AttnMode, ShardCtx, attn_mode_for, spec_for_param
from .hlo_analysis import collective_bytes, hlo_cost, roofline_terms
from .mesh import make_production_mesh

# long_500k eligibility (DESIGN.md §5): SSM/hybrid natively; mistral-nemo
# via an explicit sliding-window-4096 variant.
LONG_OK = {"rwkv6-3b", "jamba-v0.1-52b"}
LONG_SWA = {"mistral-nemo-12b": 4096}

ARCHS = ["whisper-base", "phi-3-vision-4.2b", "llama3.2-3b", "granite-8b",
         "rwkv6-3b", "granite-34b", "jamba-v0.1-52b", "kimi-k2-1t-a32b",
         "mistral-nemo-12b", "deepseek-moe-16b"]


def make_ctx(cfg: ModelConfig, shape: InputShape, mesh) -> ShardCtx:
    multi = "pod" in mesh.axis_names
    dp_axes = ("pod", "data") if multi else ("data",)
    ms = mesh.shape["model"]
    mode = attn_mode_for(cfg.attn.num_heads, cfg.attn.num_kv_heads, ms,
                         shape.kind, shape.global_batch)
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    shard_batch = shape.global_batch % dp_total == 0 and \
        shape.global_batch >= dp_total
    return ShardCtx(mesh=mesh, dp_axes=dp_axes, model_axis="model",
                    attn_mode=mode, shard_batch=shard_batch)


def _maybe(mesh, shape_tuple, spec):
    """NamedSharding, dropping axes that don't divide the dimension."""
    # left-pad shorter specs with None: stacked (repeats, ...) params keep
    # their per-layer rule on the trailing dims
    entries = [None] * (len(shape_tuple) - len(spec)) + list(spec) \
        if len(spec) < len(shape_tuple) else list(spec)[:len(shape_tuple)]
    fixed = []
    for dim, e in zip(shape_tuple, entries):
        if e is None:
            fixed.append(None)
            continue
        axes = e if isinstance(e, tuple) else (e,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        fixed.append(e if dim % size == 0 else None)
    return NamedSharding(mesh, P(*fixed))


def param_shardings(mesh, params_shapes, cfg: Optional[ModelConfig] = None,
                    zero1: bool = False):
    """Partition specs for a param-shaped tree. zero1=True (optimizer
    states of >=30B models) additionally shards the first divisible free
    dim over the dp axes — ZeRO-1: the elementwise update runs fully
    sharded; XLA inserts one all-gather of the updated params per step."""
    multi = "pod" in mesh.axis_names
    dp = ("pod", "data") if multi else "data"
    dp_total = int(np.prod([mesh.shape[a] for a in
                            (("pod", "data") if multi else ("data",))]))
    two_d = cfg is not None and cfg.moe is not None \
        and cfg.moe.shard_experts_2d

    def one(path, leaf):
        parts = []
        for k in path:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
        name = parts[-1] if parts else ""
        if two_d and name in ("expert_up", "expert_gate"):
            return _maybe(mesh, leaf.shape, ("model", None, dp))
        if two_d and name == "expert_down":
            return _maybe(mesh, leaf.shape, ("model", dp, None))
        spec = spec_for_param("/".join(parts), "model")
        entries = [None] * (len(leaf.shape) - len(spec)) + list(spec) \
            if len(spec) < len(leaf.shape) else list(spec)[:len(leaf.shape)]
        if zero1:
            for i, (dim, e) in enumerate(zip(leaf.shape, entries)):
                if e is None and dim % dp_total == 0 and dim >= dp_total:
                    entries[i] = dp
                    break
        return _maybe(mesh, leaf.shape, tuple(entries))

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def cache_shardings(mesh, cache_shapes, ctx: ShardCtx, shape: InputShape):
    multi = "pod" in mesh.axis_names
    dp = ctx.dp
    kv_seq_axes = None
    if ctx.attn_mode == AttnMode.KVSEQ:
        if ctx.shard_batch:
            kv_seq_axes = "model"
        else:  # batch=1 long-context: shard seq over everything
            kv_seq_axes = ("pod", "data", "model") if multi \
                else ("data", "model")

    def one(path, leaf):
        name = None
        for k in path:
            if hasattr(k, "key"):
                name = str(k.key)
        if name in ("k", "v"):
            if ctx.attn_mode == AttnMode.KVSEQ:
                return _maybe(mesh, leaf.shape, (dp, kv_seq_axes, None, None))
            return _maybe(mesh, leaf.shape, (dp, None, "model", None))
        if name == "s":      # rwkv state (B, H, K, V)
            return _maybe(mesh, leaf.shape, (dp, None, None, None))
        if name == "x_prev":
            return _maybe(mesh, leaf.shape, (dp, None))
        if name == "h":      # mamba state (B, din, N)
            return _maybe(mesh, leaf.shape, (dp, "model", None))
        if name == "conv":   # (B, W-1, din)
            return _maybe(mesh, leaf.shape, (dp, None, "model"))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def build_lowerable(arch: str, shape_name: str, mesh, residual: str = "d"):
    """Returns (fn, args_shapes, args_shardings, meta) ready to jit/lower."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k" and arch in LONG_SWA:
        cfg = dataclasses.replace(
            cfg, attn=dataclasses.replace(cfg.attn,
                                          sliding_window=LONG_SWA[arch]))
    model = build_model(cfg)
    ctx = make_ctx(cfg, shape, mesh)
    if residual == "seq" and shape.kind in ("train", "prefill"):
        ctx = dataclasses.replace(ctx, residual="seq")
    B, S = shape.global_batch, shape.seq_len
    dp = ctx.dp
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    params_shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0)))
    p_shard = param_shardings(mesh, params_shapes, cfg)

    meta = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "attn_mode": ctx.attn_mode, "shard_batch": ctx.shard_batch,
        "residual": ctx.residual,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }

    if shape.kind == "train":
        # bf16 momentum above 30B params: fp32 optimizer state alone
        # exceeds HBM for granite-34b/jamba/kimi (EXPERIMENTS.md §Perf)
        mdt = jnp.bfloat16 if cfg.param_count() > 30e9 else jnp.float32
        opt = sgd(lr=1e-2, momentum=0.9, momentum_dtype=mdt)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        # ZeRO-1/2 above 30B: optimizer state AND gradients sharded
        # over dp (grads constrained below => the dp-psum of the backward
        # fuses into a reduce-scatter; update runs sharded; params
        # all-gathered once per step)
        zero = cfg.param_count() > 30e9
        o_shard = param_shardings(mesh, opt_shapes, cfg, zero1=zero)
        g_shard = param_shardings(mesh, params_shapes, cfg, zero1=zero) \
            if zero else None
        n_groups = 16
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        batch_shard = {
            "tokens": _maybe(mesh, (B, S), (dp, None)),
            "labels": _maybe(mesh, (B, S), (dp, None)),
        }
        if cfg.vision_tokens:
            batch_shapes["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), dt)
            batch_shard["patch_embeds"] = _maybe(
                mesh, batch_shapes["patch_embeds"].shape, (dp, None, None))
        if cfg.encoder is not None:
            batch_shapes["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder.frontend_len, cfg.d_model), dt)
            batch_shard["frames"] = _maybe(
                mesh, batch_shapes["frames"].shape, (dp, None, None))
        w_shapes = jax.ShapeDtypeStruct((B,), jnp.float32)
        w_shard = _maybe(mesh, (B,), (dp,))

        def train_step(params, opt_state, batch, weights):
            def loss_fn(p):
                return model.loss(p, batch, ctx, remat=True,
                                  example_weights=weights)
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params)
            if g_shard is not None:   # ZeRO-2: keep grads dp-sharded
                grads = jax.tree.map(
                    lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
                    grads, g_shard)
            new_p, new_o = opt.update(grads, opt_state, params, 0)
            return new_p, new_o, loss

        args_shapes = (params_shapes, opt_shapes, batch_shapes, w_shapes)
        args_shard = (p_shard, o_shard, batch_shard, w_shard)
        # tokens processed per step * 6 * active params
        meta["model_flops"] = 6.0 * cfg.active_param_count() * B * S
        return train_step, args_shapes, args_shard, meta

    if shape.kind == "prefill":
        batch_shapes = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        batch_shard = {"tokens": _maybe(mesh, (B, S), (dp, None))}
        if cfg.vision_tokens:
            batch_shapes["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), dt)
            batch_shard["patch_embeds"] = _maybe(
                mesh, batch_shapes["patch_embeds"].shape, (dp, None, None))
        if cfg.encoder is not None:
            batch_shapes["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder.frontend_len, cfg.d_model), dt)
            batch_shard["frames"] = _maybe(
                mesh, batch_shapes["frames"].shape, (dp, None, None))

        def prefill_step(params, batch):
            return model.prefill(params, batch["tokens"], ctx,
                                 frames=batch.get("frames"),
                                 extra_embeds=batch.get("patch_embeds"))

        meta["model_flops"] = 2.0 * cfg.active_param_count() * B * S
        return (prefill_step, (params_shapes, batch_shapes),
                (p_shard, batch_shard), meta)

    # ---- decode
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, S))
    c_shard = cache_shardings(mesh, cache_shapes, ctx, shape)
    tok_shapes = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_shard = _maybe(mesh, (B, 1), (dp, None))
    static = ctx.attn_mode == AttnMode.KVSEQ
    mem_shapes = None
    if cfg.encoder is not None:
        mem_shapes = jax.ShapeDtypeStruct(
            (B, cfg.encoder.frontend_len, cfg.d_model), dt)
        mem_shard = _maybe(mesh, mem_shapes.shape, (dp, None, None))

    if mem_shapes is None:
        def serve_step(params, token, cache):
            return model.decode_step(params, token, cache, ctx,
                                     static_cache=static)
        args = (params_shapes, tok_shapes, cache_shapes)
        shards = (p_shard, tok_shard, c_shard)
    else:
        def serve_step(params, token, cache, memory):
            return model.decode_step(params, token, cache, ctx,
                                     memory=memory, static_cache=static)
        args = (params_shapes, tok_shapes, cache_shapes, mem_shapes)
        shards = (p_shard, tok_shard, c_shard, mem_shard)
    meta["model_flops"] = 2.0 * cfg.active_param_count() * B
    return serve_step, args, shards, meta


def run_one(arch: str, shape_name: str, mesh_kind: str,
            out_dir: str = "experiments/dryrun",
            save_hlo: bool = False, residual: str = "d") -> dict:
    cfg = get_config(arch)
    if shape_name == "long_500k" and arch not in (LONG_OK | set(LONG_SWA)):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "wall_s": 0.0,
               "reason": "full attention; long_500k requires sub-quadratic "
                         "(DESIGN.md §5)"}
        _save(rec, out_dir)
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    try:
        fn, args, shards, meta = build_lowerable(arch, shape_name, mesh,
                                                 residual=residual)
        with mesh:
            jitted = jax.jit(fn, in_shardings=shards)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        # loop-aware costs: XLA's cost_analysis counts while bodies once,
        # underreporting scan-over-layers programs by ~num_layers
        parsed = hlo_cost(hlo)
        roof = roofline_terms(parsed, coll, n_chips=n_chips,
                              model_flops=meta.get("model_flops", 0.0))
        rec = {
            **meta, "mesh": mesh_kind, "status": "ok",
            "n_chips": n_chips,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            },
            "cost": {"flops": parsed["flops"],
                     "bytes_accessed": parsed["bytes"],
                     "xla_flops_raw": float(cost.get("flops", 0.0)),
                     "xla_bytes_raw": float(cost.get("bytes accessed", 0.0))},
            "collectives": {"total_bytes": coll.total_bytes,
                            "count": coll.count, "by_kind": coll.by_kind},
            "roofline": {
                "compute_s": roof.compute_s, "memory_s": roof.memory_s,
                "collective_s": roof.collective_s,
                "dominant": roof.dominant,
                "model_flops": roof.model_flops,
                "useful_flops_ratio": roof.useful_flops_ratio,
            },
        }
        if save_hlo:
            hpath = os.path.join(out_dir, f"{_key(rec)}.hlo.txt")
            os.makedirs(out_dir, exist_ok=True)
            with open(hpath, "w") as f:
                f.write(hlo)
    except Exception as e:  # a failure here is a bug in our sharding
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    rec["wall_s"] = round(time.time() - t0, 2)
    _save(rec, out_dir)
    return rec


def _key(rec):
    return f"{rec['arch']}_{rec['shape']}_{rec['mesh']}".replace(".", "p")


def _save(rec, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, _key(rec) + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--residual", default="d", choices=["d", "seq"])
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                key = f"{arch}_{shape}_{mk}".replace(".", "p")
                path = os.path.join(args.out, key + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[skip] {key}: cached {prev['status']}")
                        results.append(prev)
                        continue
                rec = run_one(arch, shape, mk, args.out,
                              save_hlo=args.save_hlo,
                              residual=args.residual)
                st = rec["status"]
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" c={r['compute_s']:.3e}s"
                             f" m={r['memory_s']:.3e}s"
                             f" n={r['collective_s']:.3e}s"
                             f" peakMB={rec['memory']['peak_bytes']/2**20:.0f}")
                elif st == "error":
                    extra = " " + rec["error"][:200]
                print(f"[{st}] {key} ({rec['wall_s']}s){extra}", flush=True)
                results.append(rec)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"done: {ok} ok, {sk} skipped, {err} errors")
    return 0 if err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
