"""jit'd wrappers for sparse per-link load accumulation.

Two layouts of the same computation (see ``repro.chip.mesh_noc.
SparseIncidence``):

* ``link_loads_cols`` — prefix-column plan (``SparseIncidence.col_plan``):
  per-link loads accumulate as K unrolled 1-D gathers + prefix adds over
  count-sorted links, a segment reduction with NO scatter op and no
  padding (sum of column lengths = nnz).  Exact per-link sums (bitwise
  equal to the dense einsum on integer counts), batched over leading
  axes; the chip engine's default sparse path.
* ``link_loads_csr`` — source-major entries, gather + segment-sum
  (scatter-accumulate).  Same results; the oracle the other layouts are
  tested against lives in ref.py.
* ``link_loads_csc`` — link-major (sorted) entries, Pallas prefix-sum
  kernel + boundary differences.  The TPU-throughput variant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.link_load.link_load import (BLOCK_ROWS, LANES,
                                               flat_prefix_sum_pallas)
from repro.kernels.link_load.ref import link_loads_ref


def link_loads_cols(weights, cols, inv_perm, *, n_links: int):
    """weights: (..., P) per-source counts; (cols, inv_perm): a
    ``SparseIncidence.col_plan``.  Returns (..., n_links) link loads.

    Column k gathers the (k+1)-th source of the ``len(cols[k])`` heaviest
    links and adds onto the load prefix (count-sorted link order), so the
    unrolled loop touches exactly nnz entries; the final take restores
    link-id order.  Not jitted itself — the caller traces it inside the
    engine's scan (column lengths are static metadata)."""
    w = weights.astype(jnp.float32)
    acc = jnp.zeros(w.shape[:-1] + (n_links,), jnp.float32)
    for c in cols:
        n_k = c.shape[0]
        acc = acc.at[..., :n_k].add(jnp.take(w, c, axis=-1))
    return jnp.take(acc, inv_perm, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_links",))
def link_loads_csr(weights, link_ids, src_of_entry, *, n_links: int):
    """weights (..., P) per-source counts -> (..., n_links) link loads."""
    return link_loads_ref(weights, link_ids, src_of_entry, n_links)


@functools.partial(jax.jit, static_argnames=("n_links",))
def link_loads_csc(weights, src_sorted, link_ptr, *, n_links: int):
    """weights: (P,) per-source counts; src_sorted/link_ptr: the
    ``SparseIncidence.csc`` layout.  Returns (n_links,) link loads."""
    w = jnp.take(weights.astype(jnp.float32), src_sorted)     # (nnz,)
    per = BLOCK_ROWS * LANES
    pad = per if w.shape[0] == 0 else (-w.shape[0]) % per
    if pad:
        w = jnp.pad(w, (0, pad))
    csum = flat_prefix_sum_pallas(w.reshape(-1, LANES)).reshape(-1)
    s = jnp.concatenate([jnp.zeros(1, jnp.float32), csum])    # exclusive
    return s[link_ptr[1:]] - s[link_ptr[:-1]]
