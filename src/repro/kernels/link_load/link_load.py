"""Sparse link-load accumulation kernel (Pallas, TPU target).

The per-tick NoC accounting over a CSR incidence is a segment-sum: each
entry of a source's multicast tree adds that source's packet weight to one
link.  Scatter-add has no native TPU tile shape, so the kernel uses the
classic sorted-segment formulation: with entries sorted by link id (the
``SparseIncidence.csc`` layout), per-link sums are differences of a
running prefix sum at the link boundaries,

    loads[l] = S[link_ptr[l+1]] - S[link_ptr[l]],   S = exclusive prefix sum

and the prefix sum is one VPU pass: a sequential grid over (BLOCK_ROWS,
128) tiles, the inter-block carry living in a VMEM scratch tile across grid
steps (same pattern as the MAC-GEMM accumulator).  The carry is a whole
(BLOCK_ROWS, 128) tile holding the running total in every element: Mosaic
stores vectors, not scalars, to VMEM.  The boundary gather is plain jnp in
ops.py.

Bitwise equal to ref.py on integer-valued weights.  Note the numeric
contract: the REF segment-sum is exact per link; the prefix-sum kernel is
exact while the RUNNING TOTAL of all entries stays below float32's 2**24
integer range — ops.link_loads_csr therefore defaults to the ref path and
the kernel is the TPU-throughput variant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import pallas_call

BLOCK_ROWS = 8
LANES = 128


def _inclusive_scan(x, axis: int):
    """Inclusive prefix sum along ``axis`` in log2(n) rotate-and-add steps
    (Hillis-Steele).  Mosaic has no cumsum; rotations are native."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < n:
        x = x + jnp.where(pos >= shift, pltpu.roll(x, shift, axis), 0.0)
        shift *= 2
    return x


def _prefix_sum_kernel(w_ref, o_ref, carry_ref):
    """Inclusive prefix sum of a (R, 128) array in row-major flattened
    order; grid is sequential over row blocks, carry_ref spans blocks."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    carry = carry_ref[...]                           # running total, splat
    w = w_ref[...]                                   # (BLOCK_ROWS, 128)
    row_tot = jnp.broadcast_to(w.sum(axis=1, keepdims=True), w.shape)
    rows = _inclusive_scan(row_tot, 0)               # inclusive over rows
    o_ref[...] = _inclusive_scan(w, 1) + (rows - row_tot) + carry
    carry_ref[...] = carry + rows[BLOCK_ROWS - 1:, :]


def flat_prefix_sum_pallas(w):
    """w: (R, 128) float32, R multiple of BLOCK_ROWS -> (R, 128) inclusive
    prefix sums of the row-major flattening."""
    R, C = w.shape
    assert C == LANES and R % BLOCK_ROWS == 0, (R, C)
    bs = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pallas_call(
        _prefix_sum_kernel,
        grid=(R // BLOCK_ROWS,),
        in_specs=[bs],
        out_specs=bs,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BLOCK_ROWS, LANES), jnp.float32)],
    )(w)
