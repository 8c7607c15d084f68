"""Compile the main path's kernels, the NanoGPT step and DeepSeek-V2-Lite's
latent attention and held-expert layer for one TPU v5e chip that is
described, not attached.

The TPU compiler is installed with jaxlib's TPU support; it refuses what
interpret mode accepts (loop carries Mosaic cannot lower, blocks over
the VMEM budget, programs over the chip's memory). Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and with several
test workers only the worker given this file loads it.
"""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

#: bytes of compiler temporaries the NanoGPT step may take at batch 16,
#: leaving the rest of the chip's 16 GB for weights and optimizer state
TEMP_LIMIT = 12e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [1, 10, 64])
def test_mth_smallest_pallas_compiles(one_chip, m):
    from repro.kernels.order_stats import mth_smallest_pallas

    x = _spec((32, 1000), jnp.float32, one_chip)
    compiled = jax.jit(lambda a: mth_smallest_pallas(a, m)).lower(
        x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_nanogpt_shape(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas

    q = _spec((16, 512, 6, 64), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_nanogpt_step_fits_one_chip(one_chip):
    """The m-sync training step of nanogpt-paper at its published widths
    (block 512) and batch 16 compiles for one chip, with temporaries
    well under its 16 GB."""
    from repro.launch.train import build_run

    cfg, trainer, _ = build_run("nanogpt-paper", steps=20, batch=16,
                                seq=512, policy="m_sync", m=6, workers=8,
                                time_model="sqrt")
    assert cfg.vocab_size == 50304 and cfg.d_model == 384

    def place(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                            tree)

    params = jax.eval_shape(trainer.model.init_params, jax.random.key(0))
    opt_state = jax.eval_shape(trainer.optimizer.init, params)
    batch = {"tokens": _spec((16, 512), jnp.int32, one_chip),
             "labels": _spec((16, 512), jnp.int32, one_chip),
             "loss_mask": _spec((16, 512), jnp.float32, one_chip)}
    weights = _spec((16,), jnp.float32, one_chip)
    step = _spec((), jnp.int32, one_chip)
    compiled = trainer.step_program.lower(
        place(params), place(opt_state), batch, weights, step,
        None).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < TEMP_LIMIT, mem


def test_held_expert_layer_compiles_at_deepseek_widths(one_chip):
    """A DeepSeek-V2-Lite layer of 8 held experts (d 2048, width 1408,
    top 6 of 64) and its gradient compile for one chip over a row of
    4096 tokens; the grouped products lower to Mosaic kernels."""
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe_forward
    from repro.sharding.specs import ShardCtx

    cfg = get_config("deepseek-v2-lite-ep8")
    p = jax.eval_shape(lambda k: init_moe(k, 2048, cfg.moe, "swiglu",
                                          jnp.bfloat16), jax.random.key(0))
    p = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), p)
    x = _spec((1, 4096, 2048), jnp.bfloat16, one_chip)

    def loss(p, x):
        out, stats = moe_forward(p, x, ShardCtx.null(), cfg)
        return out.astype(jnp.float32).sum() + stats["aux_loss"]

    compiled = jax.jit(jax.grad(loss)).lower(p, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_latent_attention_compiles_at_deepseek_widths(one_chip):
    """MLA at DeepSeek-V2-Lite's widths (16 heads, latent 512, rope 64)
    and its gradient compile for one chip over 1024 positions, in query
    blocks."""
    from repro.configs import get_config
    from repro.models.mla import init_mla, mla_apply

    cfg = get_config("deepseek-v2-lite-ep8")
    p = jax.eval_shape(lambda k: init_mla(k, 2048, 16, cfg.mla,
                                          jnp.bfloat16), jax.random.key(0))
    p = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), p)
    x = _spec((2, 1024, 2048), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(
        lambda p, x: mla_apply(p, x, cfg).astype(jnp.float32).sum())
    ).lower(p, x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
