"""jit'd wrappers: arbitrary shapes + float-facing helpers for the SNN stack.

Both wrappers take an ``impl`` knob, mirroring the ``link_load_impl``
convention of ``repro.chip.mesh_noc``: "pallas" selects the Pallas kernel
(compiled on TPU, interpreted elsewhere — ``repro.kernels.platform``),
"ref" the pure-jnp bit-exact oracle, and "auto" resolves to the
reference, which XLA fuses into the surrounding tick.  The two
implementations are BIT-IDENTICAL (enforced by
tests/test_kernels_explog.py), so the knob only moves wall time; the
engine's plasticity trace decay (``repro.learn``) selects "auto".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.explog.explog import (
    BLOCK_ROWS, LANES, fx_exp_pallas, fx_log_pallas,
)
from repro.kernels.explog.ref import FX_ONE, fx_exp_ref, fx_log_ref

EXPLOG_IMPLS = ("auto", "ref", "pallas")


def resolve_explog_impl(impl: str) -> str:
    """"auto" -> the reference path (fused by XLA into its caller)."""
    if impl not in EXPLOG_IMPLS:
        raise ValueError(f"unknown explog impl {impl!r}; expected one of "
                         f"{EXPLOG_IMPLS}")
    return "ref" if impl == "auto" else impl


def _shape_to_blocks(x):
    flat = x.reshape(-1)
    n = flat.shape[0]
    per = BLOCK_ROWS * LANES
    pad = (-n) % per
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), n


@functools.partial(jax.jit, static_argnames=("impl",))
def fx_exp(x, impl="auto"):
    """x: int32 s16.15 any shape -> exp(x) int32 s16.15."""
    if resolve_explog_impl(impl) == "ref":
        return fx_exp_ref(jnp.asarray(x))
    x2d, n = _shape_to_blocks(x)
    out = fx_exp_pallas(x2d)
    return out.reshape(-1)[:n].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("impl",))
def fx_log(x, impl="auto"):
    """x: int32 s16.15 any shape, > 0 -> ln(x) int32 s16.15."""
    if resolve_explog_impl(impl) == "ref":
        return fx_log_ref(jnp.asarray(x))
    x2d, n = _shape_to_blocks(x)
    out = fx_log_pallas(x2d)
    return out.reshape(-1)[:n].reshape(x.shape)


def to_fx(x_float):
    return jnp.round(jnp.asarray(x_float, jnp.float32) * FX_ONE).astype(jnp.int32)


def from_fx(x_fx):
    return x_fx.astype(jnp.float32) / FX_ONE


def fx_exp_float(x_float):
    return from_fx(fx_exp(to_fx(x_float)))


def fx_log_float(x_float):
    return from_fx(fx_log(to_fx(x_float)))
