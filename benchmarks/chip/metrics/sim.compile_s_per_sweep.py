"""Seconds per sweep that JAX spent tracing, lowering and compiling (or
loading from the persistent cache) in the measured window, from its
monitoring events. Layer: experiment and router (``exp/runner.py``,
``core/batch.py``, the program caches of ``core/batch_jax.py``)."""


def read(obs):
    c, n = obs.get("compile"), obs.get("units")
    if not c or not n:
        return None
    return (c["trace_s"] + c["lower_s"] + c["compile_s"]) / n
