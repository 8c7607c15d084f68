"""Calls that block the host on device results (the program's
``host_syncs`` counter: each ``block_until_ready`` and each host copy of
device arrays) per sweep, over every sweep the process ran: warm-up,
window and traced window. A sweep's count is fixed by its grid and its
engines, so this is the count of each sweep of the window. Layer:
device engines (``core/batch_jax.py``, ``assemble_traces``)."""


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
    return syncs / (mix["warmup_sweeps"] + n + obs.get("traced_units", 0))
