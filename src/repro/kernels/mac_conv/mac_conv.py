"""MAC-array 2D convolution kernel (Pallas, TPU target) — the paper's CONV
fetch mode.

SpiNNaker2's CONV mode changes only the *memory fetch pattern* feeding the
same 16x4 MAC array: a shift register reuses input-feature-map rows so the
SRAM fetch relaxes to 4 B / 4 clk.  The TPU analogue implemented here:

* the padded input tile lives in VMEM (the paper partitions layers to fit
  the 128 kB PE SRAM; we partition to fit VMEM),
* the (KH x KW) kernel loop re-slices that resident tile instead of
  re-fetching from HBM — the VMEM-resident reuse is the shift register,
* each tap contributes an MXU-shaped (BH*Wo, Cin) x (Cin, BCout) int8 dot
  into an output-stationary int32 accumulator.

Grid: (batch, out-row blocks, out-channel blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import pallas_call


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, bh, wo, sh, sw, kh, kw):
    """x_ref: (1, Hp, Wp, Cin) padded input (whole image resident in VMEM);
    w_ref: (kh, kw, Cin, BCout); o_ref: (1, bh, wo, BCout)."""
    i = pl.program_id(1)
    x = x_ref[0]                                        # (Hp, Wp, Cin)
    cin = x.shape[-1]
    acc = jnp.zeros_like(acc_ref)
    for dh in range(kh):
        row0 = i * bh * sh + dh
        rows = jax.lax.dynamic_slice(
            x, (row0, 0, 0), (sh * (bh - 1) + 1, x.shape[1], cin))
        rows = jax.lax.slice(rows, (0, 0, 0), rows.shape, (sh, 1, 1))  # (bh, Wp, Cin)
        for dw in range(kw):
            cols = jax.lax.slice(rows, (0, dw, 0),
                                 (bh, dw + sw * (wo - 1) + 1, cin),
                                 (1, sw, 1))            # (bh, wo, Cin)
            a = cols.reshape(bh * wo, cin).astype(jnp.int32)
            w = w_ref[dh, dw].astype(jnp.int32)         # (Cin, BCout)
            acc += jax.lax.dot_general(
                a, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).reshape(acc.shape)
    acc_ref[...] = acc
    o_ref[0] = acc_ref[...].reshape(bh, wo, -1)


def mac_conv2d_pallas(x, w, *, stride=(1, 1), bh=8, bcout=128):
    """x: (B, Hp, Wp, Cin) int8/uint8 PRE-PADDED; w: (KH, KW, Cin, Cout).

    Returns (B, Ho, Wo, Cout) int32 with Ho = (Hp-KH)//sh + 1.
    Ho must be a multiple of bh and Cout of bcout (ops wrapper pads).
    """
    B, Hp, Wp, Cin = x.shape
    KH, KW, _, Cout = w.shape
    sh, sw = stride
    Ho = (Hp - KH) // sh + 1
    Wo = (Wp - KW) // sw + 1
    assert Ho % bh == 0 and Cout % bcout == 0, (Ho, bh, Cout, bcout)
    grid = (B, Ho // bh, Cout // bcout)
    return pallas_call(
        functools.partial(_conv_kernel, bh=bh, wo=Wo, sh=sh, sw=sw,
                          kh=KH, kw=KW),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, Cin), lambda b, i, j: (b, 0, 0, 0)),
            pl.BlockSpec((KH, KW, Cin, bcout), lambda b, i, j: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bh, Wo, bcout), lambda b, i, j: (b, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Cout), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bh * Wo, bcout), jnp.int32)],
    )(x, w)
