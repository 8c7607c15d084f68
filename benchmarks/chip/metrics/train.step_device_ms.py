"""Milliseconds per traced step in which an operation ran on the device
(union of the trace's operation intervals, mean over the chips). Layer:
trainer step."""


def read(obs):
    t, n = obs.get("trace"), obs.get("traced_units")
    if not t or not n or not t["devices"]:
        return None
    return 1e3 * t["busy_s"] / n
