"""Model FLOP utilisation of the window, in percent, for the cut
DeepSeek-V2-Lite: the forward and backward operations that each
participating token requires (``flops_moe.py``, no recomputation), with
the held experts at the rows they computed (``moe_held_rows`` per step
over the step's tokens), times ``train_tokens_per_s``, over the chips'
bf16 peak. Layer: trainer step (``train/trainer.py``, ``models/``,
``optim/``)."""

from benchmarks.chip.flops_moe import train_flops_per_token
from benchmarks.chip.registry import metric_reader


def read(obs):
    rate = obs.get("rates", {}).get("train_tokens_per_s")
    cfg, pk = obs.get("config"), obs.get("peaks")
    if not rate or not cfg or not pk:
        return None
    per_step = metric_reader("train.moe_held_rows_per_step").read(obs)
    if per_step is None:
        return None
    tokens = cfg["batch_per_chip"] * obs["chips"] * cfg["block_size"]
    per_token = train_flops_per_token(cfg, per_step / tokens)
    return 100.0 * per_token * rate / (obs["chips"] * pk["bf16_flops"])
