"""Fused LIF neuron-update kernel (Pallas, TPU target).

The PE's per-tick neuron loop (decay -> integrate -> threshold -> reset ->
refractory) fused into one VPU pass over a (256, 128) neuron tile; each
lane is one neuron, mirroring how the Arm core iterates neurons in SRAM
while the exp accelerator supplies the decay constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call
from repro.kernels.lif.ref import FRAC, fx_mul

BLOCK_ROWS = 256
LANES = 128


def _lif_kernel(v_ref, ref_ref, isyn_ref, vo_ref, refo_ref, sp_ref, *,
                alpha, v_th, v_reset, ref_ticks, v_min=None):
    v = v_ref[...].astype(jnp.int32)
    rc = ref_ref[...].astype(jnp.int32)
    isyn = isyn_ref[...].astype(jnp.int32)
    active = rc <= 0
    v1 = fx_mul(v, jnp.int32(alpha)) + isyn
    if v_min is not None:
        v1 = jnp.maximum(v1, jnp.int32(v_min))
    spike = active & (v1 >= v_th)
    vo_ref[...] = jnp.where(spike, v_reset, jnp.where(active, v1, v))
    refo_ref[...] = jnp.where(spike, ref_ticks, jnp.maximum(rc - 1, 0))
    sp_ref[...] = spike.astype(jnp.int32)


def lif_step_pallas(v, ref_ct, i_syn, *, alpha, v_th, v_reset, ref_ticks,
                    v_min=None):
    """All inputs (R, 128) int32; R multiple of BLOCK_ROWS."""
    R, C = v.shape
    assert C == LANES and R % BLOCK_ROWS == 0
    kernel = functools.partial(_lif_kernel, alpha=alpha, v_th=v_th,
                               v_reset=v_reset, ref_ticks=ref_ticks,
                               v_min=v_min)
    bs = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    sds = jax.ShapeDtypeStruct((R, C), jnp.int32)
    return pallas_call(
        kernel,
        grid=(R // BLOCK_ROWS,),
        in_specs=[bs, bs, bs],
        out_specs=(bs, bs, bs),
        out_shape=(sds, sds, sds),
    )(v, ref_ct, i_syn)
