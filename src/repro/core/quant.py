"""int8 quantization for the MAC-array compute path (W8A8)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mac_gemm.ops import mac_gemm


def quantize_per_axis(x, axis: int, bits: int = 8):
    """Symmetric per-slice quantization along `axis` (the contraction's
    counterpart axis keeps its own scale).  Returns (q int8, scale f32)."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axis).astype(jnp.float32)


def quantized_linear(x, wq, w_scale):
    """x: (M, K) float; wq: (K, N) int8 with per-col w_scale (N,).

    Activations are quantized per-row on the fly (the MAC array's graded
    "spike payload"), multiplied in int8 with int32 accumulation, then
    rescaled — the W8A8 serve path.
    """
    xq, x_scale = quantize_per_axis(x, axis=1)
    acc = mac_gemm(xq, wq)
    return acc.astype(jnp.float32) * x_scale[:, None] * w_scale[None, :]


def quantize_params_linear(w):
    """w: (K, N) float -> (int8, per-col scale)."""
    return quantize_per_axis(w, axis=0)
