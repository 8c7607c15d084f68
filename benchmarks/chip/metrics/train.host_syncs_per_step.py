"""Calls that block the host on device results (the program's
``host_syncs`` counter: in ``Trainer.run`` the per-step
``float(grad_sq)`` sync and the logging steps' loss read) per step, over
every ``Trainer.run`` step the process ran: the three steps compared
with the reference (each a logging step), the warm-up, the window and
the traced window. Layer: host loop and device (``Trainer.run``)."""

#: the steps before the warm-up that are compared with the reference
COMPARED_STEPS = 3


def read(obs):
    mix, n = obs.get("mix"), obs.get("units")
    if not mix or not n:
        return None
    try:
        from repro import telemetry
    except ImportError:           # a program without the counter
        return None
    syncs = telemetry.counters().get(telemetry.HOST_SYNCS)
    if syncs is None:
        return None
    steps = COMPARED_STEPS + mix["warmup_steps"] + n \
        + obs.get("traced_units", 0)
    return syncs / steps
