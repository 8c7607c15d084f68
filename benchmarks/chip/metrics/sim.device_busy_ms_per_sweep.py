"""Milliseconds per traced sweep in which an operation ran on the device
(union of the operation intervals of the trace, mean over the chips
used). Layer: device engines (``core/batch_jax.py``,
``kernels/order_stats.py``)."""


def read(obs):
    t, n = obs.get("trace"), obs.get("traced_units")
    if not t or not n or not t["devices"]:
        return None
    return 1e3 * t["busy_s"] / n
