"""Expert assignments the held experts computed per step, summed over
the expert layers (the program's ``moe_held_rows`` counter, read by
``Trainer.run`` with each step's ``grad_sq``), over every ``Trainer.run``
step of the process: the three steps compared with the reference, the
warm-up, the window and the traced window. A change to routing or
dispatch that drops or duplicates rows shows here first. Layer: experts
(``models/moe.py``)."""

#: the steps before the warm-up that are compared with the reference
COMPARED_STEPS = 3


def read(obs):
    mix, n = obs.get("mix"), obs.get("units")
    if not mix or not n:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    rows = telemetry.counters().get("moe_held_rows")
    if rows is None:
        return None
    steps = COMPARED_STEPS + mix["warmup_steps"] + n \
        + obs.get("traced_units", 0)
    return rows / steps
