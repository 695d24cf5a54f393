"""Active-source link accumulation kernel (Pallas, TPU target).

The event engine's NoC accounting is a segment reduction over the
gathered CSR rows of the active sources.  Scatter-add has no native TPU
tile shape (same constraint as ``repro.kernels.link_load``), so the
kernel uses the one-hot matmul formulation: with the gathered entries
flattened to ``(M, 1)`` link ids + per-entry weights, the grid walks the
link space in 128-lane blocks and, inside each, the entries in
``(BLOCK_M, 1)`` blocks, materializing each block's hit mask against the
lane window and accumulating into the resident output block,

    loads[l] = sum_m  w[m] * [ids[m] == l]

— a masked broadcast + lane reduction, all VPU-shaped.  M is the number
of gathered entries (active sources x max tree links).  Entries stream in
blocks because a ``(M, 1)`` column is lane-padded in VMEM: the whole
column of a 4096-PE ring (126976 entries) would take 62 MB.

The output is one (1, n_blocks * 128) row, written in (1, 128) lane
blocks: a block's second-to-last dim must be a multiple of 8 or the whole
array dim, so a (n_blocks, 128) output cut into (1, 128) rows does not
tile on the TPU.  Bitwise equal to ref.py on integer-valued weights
(every partial sum is an integer below 2**24).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call

LANES = 128
BLOCK_M = 2048


def _onehot_accum_kernel(ids_ref, w_ref, o_ref):
    """Grid (link blocks, entry blocks); entry blocks are the inner,
    sequential axis accumulating into one (1, 128) output block."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    base = pl.program_id(0) * LANES
    lane = base + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    hit = (ids_ref[...] == lane).astype(jnp.float32)    # (BLOCK_M, LANES)
    o_ref[...] += (w_ref[...] * hit).sum(axis=0, keepdims=True)


def onehot_link_accum_pallas(ids, w, *, n_links: int):
    """ids: (M,) int32 link ids (>= n_links = discard); w: (M,) float32
    entry weights.  Returns (n_links,) float32 per-link sums."""
    m = ids.shape[0]
    blocks = -(-max(n_links, 1) // LANES)
    bm = min(BLOCK_M, -(-max(m, 1) // 8) * 8)
    pad = -(-max(m, 1) // bm) * bm - m
    # padding entries weigh 0 and point at a discarded lane
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad), constant_values=n_links)
    w = jnp.pad(w, (0, pad))
    out = pallas_call(
        _onehot_accum_kernel,
        grid=(blocks, (m + pad) // bm),
        in_specs=[pl.BlockSpec((bm, 1), lambda j, k: (k, 0)),
                  pl.BlockSpec((bm, 1), lambda j, k: (k, 0))],
        out_specs=pl.BlockSpec((1, LANES), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, blocks * LANES), jnp.float32),
    )(ids.reshape(-1, 1), w.reshape(-1, 1))
    return out.reshape(-1)[:n_links]
