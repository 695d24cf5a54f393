"""Fused flash-attention kernel (Pallas, TPU target) — beyond-paper.

EXPERIMENTS.md section 4 identifies the remaining memory term of the prefill
cells as attention score-chain traffic at HLO fusion boundaries; the fix is
keeping the whole online-softmax inner loop in VMEM.  This kernel is that
fix for the TPU target: one `pallas_call` per (batch, head, q-block) whose
kv loop runs in the grid's innermost dimension with the (m, l, acc)
accumulators resident in VMEM scratch — scores never visit HBM.

It is the paper's output-stationary MAC-array discipline applied to
attention: accumulators stay put, operands stream.

Causal masking is applied per block; fully-masked future blocks are
ZEROED (their contribution) but still iterated — Pallas grids are dense.
On a real deployment `num_stages`/block sizes would be tuned per chip;
here blocks default to MXU-aligned 128s and correctness is validated
against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import pallas_call

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq, bk, nk, scale, causal):
    """Grid (B*H, nq, nk); kv index is innermost (sequential)."""
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (bq, D)
    k = k_ref[0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    if causal:
        p = jnp.where(kpos <= qpos, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, bq=128, bk=128, causal=True):
    """q, k, v: (BH, S, D) — batch*heads flattened.  Returns (BH, S, D)."""
    BH, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = 1.0 / np.sqrt(D)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, causal=causal)
    return pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
    )(q, k, v)
