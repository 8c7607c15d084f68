"""The chip check and the device numbers every result line carries.

:func:`check_device` is ``chip_smoke.check_device`` with the cell's chip
count: it prints the platform, ``device_kind`` and count, and exits
non-zero, before any result is printed, unless JAX sees enough TPUs.
"""

from __future__ import annotations

import sys


class NoChip(SystemExit):
    """Raised when JAX finds no TPU, or fewer than the cell asks for."""


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(need: int) -> dict:
    """Return the device record; raise :class:`NoChip` unless JAX sees at
    least ``need`` TPU devices."""
    import importlib.metadata

    import jax

    info = device_info()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} libtpu={libtpu}",
          file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"chip benchmark: no TPU (platform "
                     f"{info['platform']!r}); nothing was run")
    if info["count"] < need:
        raise NoChip(f"chip benchmark: the cell needs {need} TPU devices, "
                     f"JAX sees {info['count']}")
    return info


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest of ``devices``: the peak of buffers in use
    plus the peak the TPU runtime reserved for programs' temporaries,
    which it keeps apart from the buffers (0 where the backend keeps no
    statistics)."""
    def peak(d):
        st = d.memory_stats() or {}
        return st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved",
                                                       0)

    return int(max((peak(d) for d in devices), default=0))
