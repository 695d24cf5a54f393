"""jit'd wrapper: (B, S, H, D) GQA-expanded attention through the fused
Pallas flash kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.flash_attn import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention_kernel(q, k, v, *, causal=True, bq=128, bk=128):
    """q: (B, S, H, D); k, v: (B, S, H, D) (KV pre-expanded to H heads)."""
    B, S, H, D = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    bq = min(bq, S)
    bk = min(bk, S)
    out = flash_attention_pallas(fold(q), fold(k), fold(v),
                                 bq=bq, bk=bk, causal=causal)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
