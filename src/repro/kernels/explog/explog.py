"""Fixed-point exp/log accelerator kernels (Pallas, TPU target).

TPU adaptation of the SpiNNaker2 elementary-function accelerator
([10] ISCAS'17, [11] ARITH'18): s16.15 fixed-point exp/ln via iterative
shift-add over the ln(1 + 2^-k) constant ladder.  In the PE this is a
serial multiplier-free datapath next to the Arm core; on TPU the same
ladder becomes 15 vectorized compare/select steps on the VPU over a
(block_rows, 128)-lane tile — each lane is one "accelerator instance".

Bit-exact against ref.py (same integer ops); scientific accuracy vs float
exp/log is asserted in tests (rel. error < 2^-12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call
from repro.kernels.explog.ref import FRAC, FX_ONE, LN2, LOG_TABLE, _MAX_EXP_ARG

BLOCK_ROWS = 256
LANES = 128


def _fx_exp_kernel(x_ref, o_ref):
    x = jnp.clip(x_ref[...].astype(jnp.int32), -_MAX_EXP_ARG, _MAX_EXP_ARG)
    n = jnp.floor_divide(x, LN2)
    r = x - n * LN2
    y = jnp.full_like(x, FX_ONE)
    for k in range(1, 16):
        lk = LOG_TABLE[k - 1]
        take = r >= lk
        r = jnp.where(take, r - lk, r)
        y = jnp.where(take, y + (y >> k), y)
    y = y + ((y * r) >> FRAC)
    n = jnp.clip(n, -31, 31)
    y = jnp.where(n >= 0,
                  jnp.where(n >= 16, jnp.int32(2**31 - 1),
                            y << jnp.minimum(n, 15)),
                  y >> jnp.minimum(-n, 31))
    o_ref[...] = y


def _fx_log_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.int32)
    bad = x <= 0
    xs = jnp.maximum(x, 1)
    n = jnp.zeros_like(xs)
    z = xs
    for shift in (15, 8, 4, 2, 1):
        cond = z >= (FX_ONE << shift)
        z = jnp.where(cond, z >> shift, z)
        n = jnp.where(cond, n + shift, n)
    for shift in (8, 4, 2, 1, 1):
        cond = z < (FX_ONE >> (shift - 1))
        z = jnp.where(cond, z << shift, z)
        n = jnp.where(cond, n - shift, n)
    acc = n * LN2
    w = jnp.full_like(xs, FX_ONE)
    for k in range(1, 16):
        lk = LOG_TABLE[k - 1]
        w_next = w + (w >> k)
        take = w_next <= z
        w = jnp.where(take, w_next, w)
        acc = jnp.where(take, acc + lk, acc)
    acc = acc + jnp.floor_divide((z - w) << FRAC, w)
    o_ref[...] = jnp.where(bad, jnp.int32(-(2**30)), acc)


def _elementwise_call(kernel, x2d):
    R, C = x2d.shape
    assert C == LANES and R % BLOCK_ROWS == 0
    return pallas_call(
        kernel,
        grid=(R // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int32),
    )(x2d)


def fx_exp_pallas(x):
    return _elementwise_call(_fx_exp_kernel, x)


def fx_log_pallas(x):
    return _elementwise_call(_fx_log_kernel, x)
