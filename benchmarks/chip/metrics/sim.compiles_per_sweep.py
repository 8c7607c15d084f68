"""Backend-compile events per sweep in the measured window, persistent
cache loads included: how many programs each sweep builds anew. Layer:
experiment and router."""


def read(obs):
    c, n = obs.get("compile"), obs.get("units")
    if not c or not n:
        return None
    return c["compiles"] / n
