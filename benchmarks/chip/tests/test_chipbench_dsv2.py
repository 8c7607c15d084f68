"""The DeepSeek-V2-Lite cell at a tiny size on the CPU: the program's
loss and gradients against the plain reference beside the
configuration, the cell's unit of work through the training driver, and
its readers and operation counts."""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops_moe, registry, train
from benchmarks.chip.clock import CompileClock
from benchmarks.chip.run import per_layer_values

from chipbench_helpers import BENCH

CELL = "train.dsv2lite.msync"


def tiny_program_config():
    """The cut configuration's families at d 32: one dense and two expert
    layers of 2 held experts out of 4, top 2, one shared expert."""
    from repro.configs import get_config, reduced

    return dataclasses.replace(
        reduced(get_config("deepseek-v2-lite-ep8"), d_model=32,
                layers_per_stage=2, vocab=128),
        max_seq_len=16)


def tiny_cell(monkeypatch, **mix_kw):
    import repro.launch.train as launch

    prog = tiny_program_config()
    monkeypatch.setattr(launch, "get_config", lambda arch: prog)
    cell = registry.workload(BENCH, CELL)
    a, m = prog.mla, prog.moe
    cfg = dict(registry.config(cell["config"]), n_layer=3, hidden_size=32,
               num_hidden_layers=3, num_attention_heads=prog.attn.num_heads,
               kv_lora_rank=a.kv_lora_rank, qk_nope_head_dim=a.qk_nope_head_dim,
               qk_rope_head_dim=a.qk_rope_head_dim, v_head_dim=a.v_head_dim,
               intermediate_size=prog.d_ff, moe_intermediate_size=m.d_expert,
               n_shared_experts=m.num_shared_experts,
               num_experts_per_tok=m.experts_per_token,
               n_routed_experts=m.held, router_outputs=m.num_experts,
               vocab_size=128, block_size=16, batch_per_chip=8)
    mix = dict(registry.traffic(cell["traffic"]), **mix_kw)
    return cell, cfg, mix, registry.reference(cell["config"])


def test_program_loss_and_gradients_match_the_reference(monkeypatch):
    from repro.launch.train import build_run

    cell, cfg, mix, ref = tiny_cell(monkeypatch)
    _, trainer, _ = build_run(cfg["program_config"], batch=8, seq=16)
    model = trainer.model
    params = train.make_weights(
        jax.eval_shape(model.init_params, jax.random.key(0)), 11, 3,
        cfg["init"])
    # a larger spread than the published 0.006, so that routing and the
    # latent norm see scores far from uniform
    params = jax.tree.map(lambda a: a * 20.0 if a.ndim > 2 else a, params)
    tokens, labels = train.ZipfFeed(128, 16, 8, 1.2, 5).batch_at(0)
    w = ref.example_weights(cfg, 8)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
             "loss_mask": jnp.ones(tokens.shape)}
    (loss, met), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch, example_weights=jnp.asarray(w)),
        has_aux=True))(params)
    want_loss, want = ref.loss_and_grads(params, tokens, labels, w, cfg)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert float(met["aux_loss"]) > 0
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-3,
                                   atol=2e-6, err_msg=str(path))


def test_the_cell_runs_and_matches_the_reference(monkeypatch):
    cell, cfg, mix, ref = tiny_cell(monkeypatch, steps_per_call=2,
                                    warmup_steps=1)
    run = train.run_cell(cell, cfg, mix, ref, 2**31 + 17, 0.5, False,
                         time.perf_counter(), CompileClock())
    assert run.correct, run.checks
    # 6 of 8 one-row workers, 16 tokens each
    assert run.obs["tokens_per_step"] == 6 * 16
    assert run.rates["train_tokens_per_s"] > 0


def test_the_fp8_control_and_the_half_batch_fault_fail_a_limit(monkeypatch):
    from benchmarks.chip import controls

    cell, cfg, mix, ref = tiny_cell(monkeypatch)
    out = controls.train_readings(cell, cfg, mix, ref, 2**31 + 3)
    lim = mix["limits"]
    assert all(v <= lim[k] for k, v in out["program"].items()), out
    for kind in ("control", "half_batch"):
        assert any(v > lim[k] for k, v in out[kind].items()), (kind, out)


def test_the_new_readers(monkeypatch):
    from repro import telemetry

    cell = registry.workload(BENCH, CELL)
    cfg = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    steps = 3 + mix["warmup_steps"] + 40 + mix["trace_steps"]
    monkeypatch.setattr(telemetry, "counters",
                        lambda: {"moe_held_rows": 123_000 * steps,
                                 "host_syncs": steps})
    obs = {"rates": {"train_tokens_per_s": 2.0e4}, "config": cfg,
           "peaks": {"bf16_flops": 197e12}, "chips": 1, "mix": mix,
           "units": 40, "traced_units": mix["trace_steps"]}
    vals = per_layer_values(BENCH, cell, obs)
    assert vals["train.moe_held_rows_per_step"]["value"] == 123_000
    per_token = flops_moe.train_flops_per_token(cfg, 123_000 / 32768)
    assert vals["train.dsv2lite.mfu"] == {
        "value": pytest.approx(100 * per_token * 2.0e4 / 197e12),
        "unit": "%"}
    assert vals["train.host_syncs_per_step"]["value"] == 1.0
    # a program without the counter: the readers say nothing
    monkeypatch.setattr(telemetry, "counters", lambda: {"host_syncs": 1})
    assert "train.dsv2lite.mfu" not in per_layer_values(BENCH, cell, obs)


def test_deepseek_v2_lite_operations_match_the_hand_count():
    cfg = registry.config("deepseek-v2-lite-ep8")
    mla = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    assert mla == pytest.approx(13.8e6, rel=3e-3)          # per layer
    attn = 2 * mla + 2 * 4096 * 16 * (192 + 128)
    hand = (attn + 6 * 2048 * 10944                         # dense layer
            + 5 * (attn + 6 * 2048 * 2816 + 2 * 2048 * 64)  # expert layers
            + 3.75 * 6 * 2048 * 1408                        # held rows
            + 2 * 2048 * 12800)                             # head
    assert flops_moe.forward_flops_per_token(cfg, 3.75) == hand
    assert flops_moe.train_flops_per_token(cfg, 3.75) == 3 * hand
    assert flops_moe.grouped_matmul_bytes(10, 4, 3, 2) == 2 * (
        80 + 72 + 120 + 40)


def test_the_configuration_file_keeps_the_published_keys():
    cfg = registry.config("deepseek-v2-lite-ep8")
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "deepseek-v2-lite-ep8")
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (27, 64, 102400)
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 8, 12800)
    assert cfg["router_outputs"] == 64 and cfg["num_experts_per_tok"] == 6
    assert (cfg["hidden_size"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"]) == (
        2048, 512, 128, 64, 128, 1408, 10944)
