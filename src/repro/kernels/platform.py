"""Where a Pallas kernel runs: interpreted on CPU, compiled elsewhere.

The choice is made by the platform a program is lowered for, not by a
flag or an environment variable: :func:`pallas_call` traces both the
interpreted and the compiled variant and ``lax.platform_dependent``
lowers only the one that matches. A CPU run (the tests) interprets the
kernel body; a TPU run, and an ahead-of-time compile for a described
TPU topology, gets the compiled Mosaic kernel.
"""

from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl

__all__ = ["pallas_call"]


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` whose mode follows the
    platform the enclosing program is lowered for."""
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    compiled = pl.pallas_call(kernel, **kwargs)

    def call(*args):
        return lax.platform_dependent(*args, cpu=interpreted,
                                      default=compiled)
    return call
