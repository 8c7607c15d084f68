"""Training launcher: the end-to-end driver.

Examples (CPU, reduced scale):
  PYTHONPATH=src python -m repro.launch.train --arch nanogpt-paper \
      --steps 200 --policy m_sync --m 6 --workers 8 --time-model sqrt
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --reduced \
      --steps 50 --policy auto_m

The command line runs on one device. :func:`build_run` takes a
data-parallel ``ShardCtx`` for a mesh (``chip_smoke.py --four-chips``
drives it on four chips).
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

import numpy as np

from ..configs import get_config, reduced as reduce_cfg
from ..core import (FixedTimes, SyncMode, SyncPolicy, exponential_times,
                    truncated_normal_times, uniform_times)
from ..data import SyntheticLM
from ..models import build_model
from ..optim import adamw, cosine_schedule, sgd
from ..sharding.specs import ShardCtx
from ..train import Trainer, save_checkpoint
from .compile_cache import use_compile_cache


def build_time_model(name: str, n: int):
    if name == "none":
        return None
    if name == "sqrt":
        return FixedTimes.sqrt_law(n)
    if name == "linear":
        return FixedTimes.linear(n)
    if name == "uniform":
        return uniform_times(np.ones(n), half_width=0.5)
    if name == "exp":
        return exponential_times(lam=1.0, n=n)
    if name == "truncnorm_sqrt":
        return truncated_normal_times(np.sqrt(np.arange(1, n + 1)), 0.5)
    raise ValueError(name)


def build_run(arch: str = "nanogpt-paper", *, steps: int = 100,
              batch: int = 16, seq: int = 128, lr: float = 3e-3,
              optimizer: str = "adamw", policy: str = "full",
              m: Optional[int] = None, deadline: Optional[float] = None,
              workers: int = 8, time_model: str = "sqrt",
              remat: bool = False, seed: int = 0, reduced: bool = False,
              d_model: int = 256, ctx: Optional[ShardCtx] = None):
    """The launcher's run, built but not started:
    ``(cfg, trainer, data)``. :func:`main` maps its flags onto these
    arguments; ``ctx`` (not a flag) puts the trainer on a
    data-parallel mesh. The configuration runs as the registry states
    it (a cut it states included); ``reduced=True`` asks for the
    CPU-scale variant at ``d_model``. Layers are recomputed in the
    backward pass if ``remat`` or the configuration asks for it."""
    cfg = get_config(arch)
    if reduced:
        cfg = reduce_cfg(cfg, d_model=d_model, layers_per_stage=2,
                         vocab=min(cfg.vocab_size, 2048))
    model = build_model(cfg)

    sched = cosine_schedule(lr, warmup=max(steps // 20, 1), total=steps)
    opt = {"adamw": lambda: adamw(lr=sched),
           "sgd": lambda: sgd(lr=sched),
           "sgdm": lambda: sgd(lr=sched, momentum=0.9)}[optimizer]()

    sync = SyncPolicy(mode=SyncMode(policy), m=m, deadline=deadline)
    tm = build_time_model(time_model, workers)
    if sync.mode != SyncMode.FULL and tm is None:
        raise SystemExit("--policy requires a --time-model")

    trainer = Trainer(model, opt, n_workers=workers, sync_policy=sync,
                      time_model=tm, ctx=ctx, remat=remat or cfg.remat,
                      seed=seed)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       batch_size=batch, seed=seed)
    return cfg, trainer, data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="nanogpt-paper")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale same-family variant")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "sgdm"])
    ap.add_argument("--policy", default="full",
                    choices=["full", "m_sync", "auto_m", "deadline"])
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--deadline", type=float, default=None)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--time-model", default="sqrt",
                    choices=["none", "sqrt", "linear", "uniform", "exp",
                             "truncnorm_sqrt"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg, trainer, data = build_run(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, optimizer=args.optimizer, policy=args.policy,
        m=args.m, deadline=args.deadline, workers=args.workers,
        time_model=args.time_model, remat=args.remat, seed=args.seed,
        reduced=args.reduced, d_model=args.d_model)
    state = trainer.init_state()

    print(f"arch={cfg.name} params={cfg.param_count() / 1e6:.1f}M "
          f"policy={args.policy} workers={args.workers} "
          f"time_model={args.time_model}")
    hist = trainer.run(state, iter(data), num_steps=args.steps,
                       log_every=args.log_every)
    for s, t, l, m in zip(hist.steps, hist.sim_seconds, hist.losses,
                          hist.m_used):
        print(f"step {s:5d}  sim {t:9.1f}s  loss {l:7.4f}  m={m}")
    if args.ckpt:
        fs = trainer.final_state
        save_checkpoint(args.ckpt, fs.params, fs.opt_state, fs.step)
        print(f"saved checkpoint to {args.ckpt}")
    print(json.dumps({"final_loss": hist.losses[-1],
                      "sim_seconds": hist.sim_seconds[-1],
                      "wall_seconds": hist.wall_seconds[-1]}))


if __name__ == "__main__":
    main()
