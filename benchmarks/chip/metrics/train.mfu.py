"""Model FLOP utilisation of the window, in percent: the forward and
backward operations that each participating token requires (from the
configuration's shapes, no recomputation) times ``train_tokens_per_s``,
over the chips' bf16 peak. Layer: trainer step (``train/trainer.py``,
``models/``, ``optim/``)."""

from benchmarks.chip.flops import decoder_train_flops_per_token


def read(obs):
    rate = obs.get("rates", {}).get("train_tokens_per_s")
    cfg, pk = obs.get("config"), obs.get("peaks")
    if not rate or not cfg or not pk:
        return None
    per_token = decoder_train_flops_per_token(
        cfg["n_layer"], cfg["n_embd"], cfg["d_ff"], cfg["vocab_size"],
        cfg["block_size"])
    return 100.0 * per_token * rate / (obs["chips"] * pk["bf16_flops"])
