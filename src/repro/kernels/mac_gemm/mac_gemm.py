"""MAC-array GEMM kernel (Pallas, TPU target).

TPU adaptation of the SpiNNaker2 16x4 8-bit output-stationary MAC array
(paper Fig. 8, "MM mode").  The architectural insight carried over:

* output-stationary accumulation — the int32 accumulator tile lives in VMEM
  scratch across the whole K loop (the paper keeps accumulators in the MAC
  registers while streaming operands from SRAM),
* operand streaming — A tiles stream from HBM to VMEM like the paper's
  128 bit/clk SRAM port; B tiles stream like its NoC port,
* 8-bit multipliers with wide accumulation (int8 x int8 -> int32), giving
  the 2x int8 MXU throughput on TPU (394 TOPS vs 197 TFLOP/s bf16).

Scaling up: the paper's 4x16 array becomes a 128x128 MXU tile; blocks are
(BM, BK) x (BK, BN) with 128-multiples so every dot hits the systolic array
natively.  The 8-bit operands go into the dot as they are, accumulating in
int32 (``preferred_element_type``): Mosaic has no int32 x int32 matmul.
Bitwise equal to ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import pallas_call

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128


def _mac_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Grid (M/BM, N/BN, K/BK); K is the innermost (sequential) dimension."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def mac_gemm_pallas(a: jax.Array, b: jax.Array, *, bm=DEFAULT_BM,
                    bn=DEFAULT_BN, bk=DEFAULT_BK) -> jax.Array:
    """a: (M, K) int8/uint8; b: (K, N) int8/uint8 -> (M, N) int32.

    Shapes must be multiples of the block sizes (ops.mac_gemm pads).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk
    return pallas_call(
        functools.partial(_mac_gemm_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )(a, b)
