"""jit'd public wrappers around the MAC GEMM kernel (padding + dequant)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mac_gemm.mac_gemm import (
    DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, mac_gemm_pallas,
)


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def mac_gemm(a, b, *, bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK):
    """int8/uint8 GEMM with int32 accumulation; pads to block multiples."""
    M, K = a.shape
    _, N = b.shape
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    out = mac_gemm_pallas(ap, bp, bm=bm, bn=bn, bk=bk)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def mac_gemm_dequant(a, b, a_scale, b_scale, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                     bk=DEFAULT_BK):
    """W8A8 path: int32 accumulate then per-row/col rescale to f32."""
    acc = mac_gemm(a, b, bm=bm, bn=bn, bk=bk)
    return acc.astype(jnp.float32) * a_scale[:, None] * b_scale[None, :]
