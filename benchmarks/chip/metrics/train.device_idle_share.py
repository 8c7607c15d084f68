"""Share of the traced window in which no operation ran on the device:
1 - busy / window. Layer: host loop and device (``Trainer.run``, the
feed, the per-step sync)."""


def read(obs):
    t, w = obs.get("trace"), obs.get("window_s")
    if not t or not w or not t["devices"]:
        return None
    return 1.0 - t["busy_s"] / w
