"""jit'd wrappers for the event-mode active-source NoC accumulation.

Layouts of the same computation (see ``repro.kernels.event_gather.ref``):

* ``event_link_loads`` with ``impl="gather"`` — gather the active
  sources' padded CSR rows, flatten, one ``segment_sum``.  O(cap * L)
  work, independent of P; the jnp reference path of the compacted-index
  formulation.
* ``impl="pallas"`` — same gather stage, accumulation through the
  one-hot lane kernel (``event_gather.onehot_link_accum_pallas``;
  ``repro.kernels.platform`` compiles it on TPU, interprets it elsewhere).
* ``impl="auto"`` — resolved by the ENGINE (``repro.chip.mesh_noc.
  NocAccounting.event_plan``) to the dense-weight column plan, which is
  O(nnz) with no scatter; the compacted-index impls here are the
  TPU-shaped variants and the oracle-tested reference semantics.

All impls sum the same exact integer-valued terms per link (quiescent
lanes contribute exact 0.0), so they agree bitwise with each other and
with the dense einsum whenever ``idx`` covers every nonzero weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.event_gather.event_gather import onehot_link_accum_pallas
from repro.kernels.event_gather.ref import event_link_loads_ref

EVENT_GATHER_IMPLS = ("auto", "gather", "pallas")


def active_source_set(weights, cap: int):
    """Compact the nonzero lanes of ``weights`` (..., P) into a (cap,)
    index buffer (one sort — ascending ids first, sentinel P after).
    Returns (idx, n_active); ``n_active > cap`` flags overflow (callers
    fall back to the dense path to stay exact)."""
    P_ = weights.shape[-1]
    act = weights != 0
    dt = jnp.uint16 if P_ <= 0xFFFF else jnp.int32
    tags = jnp.where(act, jnp.arange(P_, dtype=dt),
                     jnp.asarray(P_, dt))
    idx = jax.lax.sort(tags)[..., :cap].astype(jnp.int32)
    return idx, act.sum(axis=-1).astype(jnp.int32)


def gather_entries(idx, weights, rows_padded):
    """Gather stage shared by both compacted impls: (cap,) active ids ->
    flattened (cap * L,) link ids + per-entry float32 weights (0.0 on
    unused lanes)."""
    P_ = weights.shape[-1]
    safe = jnp.minimum(idx, P_ - 1)
    w = jnp.where(idx < P_, weights[safe].astype(jnp.float32), 0.0)
    ids = rows_padded[safe]                              # (cap, L)
    w_entry = jnp.broadcast_to(w[:, None], ids.shape)
    return ids.reshape(-1), w_entry.reshape(-1)


@functools.partial(jax.jit, static_argnames=("n_links",))
def event_link_loads_gather(idx, weights, rows_padded, *, n_links: int):
    ids, w = gather_entries(idx, weights, rows_padded)
    # one extra segment swallows the padding sentinel (id == n_links)
    return jax.ops.segment_sum(w, ids, num_segments=n_links + 1)[:n_links]


@functools.partial(jax.jit, static_argnames=("n_links",))
def event_link_loads_pallas(idx, weights, rows_padded, *, n_links: int):
    ids, w = gather_entries(idx, weights, rows_padded)
    return onehot_link_accum_pallas(ids, w, n_links=n_links)


def event_link_loads(idx, weights, rows_padded, *, n_links: int,
                     impl: str = "gather"):
    """Per-link loads from a compacted active-source buffer; see module
    docstring for the impl menu ("auto" resolves to "gather" here — the
    engine-level auto lives on ``NocAccounting.event_plan``)."""
    if impl not in EVENT_GATHER_IMPLS:
        raise ValueError(f"unknown event_gather impl {impl!r}; "
                         f"expected one of {EVENT_GATHER_IMPLS}")
    if impl == "pallas":
        return event_link_loads_pallas(idx, weights, rows_padded,
                                       n_links=n_links)
    return event_link_loads_gather(idx, weights, rows_padded,
                                   n_links=n_links)
