"""Event-triggered MAC layer — the paper's hybrid SNN/DNN mechanism
(Sec. II: "the MAC array could be run not frame-based, but in an
event-triggered fashion ... graded weight x graded activity-related input").

A batch of graded spike events (values + active mask) hits an int8 weight
matrix; only active rows are dispatched to the MAC array.  Dispatch uses
the same sort-to-capacity scheme as the MoE router (models/moe.py) — both
are instances of SpiNNaker2 multicast: keys pick destinations, payloads are
graded values.

Energy: proportional to dispatched events (activity), not to the frame
size — the DVFS principle applied to the MAC datapath.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import paper
from repro.core.quant import quantize_per_axis
from repro.kernels.mac_gemm.ops import mac_gemm


def event_mac(values, active, wq, w_scale, *, capacity=None):
    """values: (T, K) float graded payloads; active: (T,) bool event mask;
    wq: (K, N) int8.  Returns (out (T, N) f32, n_dispatched).

    Inactive rows produce exact zeros and are never multiplied: active rows
    are compacted to a fixed-capacity buffer (sorted dispatch), multiplied,
    and scattered back.
    """
    T, K = values.shape
    C = capacity or T
    idx = jnp.nonzero(active, size=C, fill_value=T)[0]       # (C,)
    src = jnp.concatenate([values, jnp.zeros((1, K), values.dtype)], axis=0)
    dispatched = src[idx]                                    # (C, K)
    xq, x_scale = quantize_per_axis(dispatched, axis=1)
    acc = mac_gemm(xq, wq)
    yq = acc.astype(jnp.float32) * x_scale[:, None] * w_scale[None, :]
    out = jnp.zeros((T + 1, wq.shape[1]), jnp.float32).at[idx].set(yq)
    return out[:T], jnp.sum(active.astype(jnp.int32))


def event_mac_tick(spikes, w_eff):
    """One tick of the event-triggered MAC: accumulate one weight row per
    spiking input ("graded weight x activity-related input", Sec. II).

    spikes: (K,) 0/1 event vector arriving this tick; w_eff: (K, N) f32
    dequantized weights.  Returns (out (N,), n_events) — ticks with no
    events produce exact zeros and dispatch nothing, which is what the
    per-tick chip engine (repro.chip) prices: energy follows activity.
    """
    s = spikes.astype(jnp.float32)
    n_events = s.sum().astype(jnp.int32)
    return jnp.matmul(s, w_eff, precision="highest"), n_events


def event_mac_energy_j(n_events, k, n, *, tops_per_w=None):
    """Energy of event-triggered MAC ops from the paper's measured
    efficiency (Fig. 15: 1.47 TOPS/W at PL2, x1.56 hardware bug factor)."""
    tops_per_w = tops_per_w or paper.MAC_TOPS_PER_W[(0.50, 200e6)]
    ops = 2.0 * float(n_events) * k * n
    return ops / (tops_per_w * 1e12)


def frame_mac_energy_j(t, k, n, **kw):
    return event_mac_energy_j(t, k, n, **kw)
