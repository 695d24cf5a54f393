"""How a Pallas kernel runs, decided by the platform it is lowered for.

On a TPU, Mosaic compiles the kernel.  Every other backend has no Mosaic
lowering, so there the Pallas interpreter runs the same kernel body.  The
choice is made at lowering time (``jax.lax.platform_dependent``), not from
the process's default backend: a program placed on the host CPU of a TPU
machine still runs its kernels, interpreted, and a program compiled ahead
of time for a described TPU gets the compiled kernel.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` compiled on TPU, interpreted
    elsewhere.  Takes every ``pl.pallas_call`` argument but ``interpret``."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call
