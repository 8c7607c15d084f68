"""Tiny versions of the cells, for the CPU: same code paths, small
shapes. The simulator runs on the ``jax`` engine (``fastest`` would pick
a host engine on the CPU, whose NumPy draws the reference does not
follow)."""

from __future__ import annotations

import dataclasses

from benchmarks.chip import registry

BENCH = registry.load_benchmark()


def sim_cell(name: str, **mix_kw):
    cell = registry.workload(BENCH, name)
    cfg = dict(registry.config(cell["config"]), n=24, K=40,
               seeds_per_sweep=4, record_every=40)
    mix = dict(registry.traffic(cell["traffic"]), backend="jax",
               **mix_kw)
    if mix["grid"]:
        mix["grid"] = {"m": [1, 3, 12]}
    return cell, cfg, mix, registry.reference(cell["config"])


def tiny_model_config():
    from repro.configs import get_config
    from repro.configs.base import Stage

    base = get_config("nanogpt-paper")
    # float32: at this size the program then reads far under every limit
    # and each fault or control far over one
    return dataclasses.replace(
        base, d_model=32, d_ff=64, vocab_size=512, max_seq_len=16,
        dtype="float32",
        stages=(Stage(pattern=base.stages[0].pattern, repeats=2),),
        attn=dataclasses.replace(base.attn, num_heads=2, num_kv_heads=2,
                                 head_dim=16))


def train_cell(monkeypatch, traffic="msync_steps", chips=1, **mix_kw):
    """A training cell of ``nanogpt-paper`` under ``traffic`` on ``chips``
    devices, with a 2-layer, d=32 NanoGPT: the launcher is steered to it
    through its config lookup."""
    import repro.launch.train as launch

    tiny = tiny_model_config()
    monkeypatch.setattr(launch, "get_config", lambda arch: tiny)
    mix = dict(registry.traffic(traffic), **mix_kw)
    cell = {"name": f"train.nanogpt.{traffic}", "config": "nanogpt-paper",
            "traffic": traffic, "chips": chips}
    cfg = dict(registry.config(cell["config"]), n_layer=2, n_head=2,
               n_embd=32, d_ff=64, vocab_size=512, block_size=16,
               batch_per_chip=8)
    return cell, cfg, mix, registry.reference(cell["config"])
