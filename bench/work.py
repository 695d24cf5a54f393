"""The work a tick needs, counted from what the network does.

These counts are the numerators of the roofline and utilization shares.
They count the network's work, not the arrays an implementation happens
to store, so no correct implementation, dense or event-driven, can read
above 100%.  Work a count leaves out only lowers the share.
"""
from __future__ import annotations

import numpy as np

# an s16.15 synaptic weight of the synfire ring has |w| <= 9830, so the
# narrowest exact width is int16
WEIGHT_BYTES = 2
OPS_PER_SYN_EVENT = 2            # one multiply-add
# membrane: after each tick it lies in [v_min, v_th) = [-1, 1) v_th, so
# int16 s0.15 holds it exactly; the refractory count (0..2) takes 2 bits
STATE_BYTES_PER_NEURON = 2 + 0.25


def record_bytes(rec: np.ndarray) -> float:
    """Bytes to write ``rec`` once at the narrowest width that holds its
    values exactly: 1 bit for 0/1 values, 1, 2 or 4 bytes for other
    integers, 4 bytes for float32 values."""
    a = np.asarray(rec)
    if a.size == 0:
        return 0.0
    if np.issubdtype(a.dtype, np.floating) and not np.all(a == np.round(a)):
        return 4.0 * a.size
    lo, hi = float(a.min()), float(a.max())
    if lo >= 0 and hi <= 1:
        return a.size / 8
    for nbytes in (1, 2):
        lim = 2 ** (8 * nbytes - 1)
        if -lim <= lo and hi < lim:
            return float(nbytes * a.size)
    return 4.0 * a.size


def synfire_tick_work(rec: dict, deg_ff: np.ndarray, deg_inh: np.ndarray,
                      d_exc: int, d_inh: int) -> dict:
    """Operations and bytes one tick of the synfire ring needs, averaged
    over the ``T`` ticks of one job's records ``rec``.

    1. Synaptic events delivered inside the job: an excitatory spike of
       PE p at tick t reaches PE p+1 at t + ``d_exc`` and drives the
       ``deg_ff[p+1, e]`` synapses of its source neuron there; an
       inhibitory spike reaches its own PE's ``deg_inh[p, i]`` synapses
       at t + ``d_inh``.  Each event is ``OPS_PER_SYN_EVENT`` operations
       and reads one weight of ``WEIGHT_BYTES``.
    2. Every neuron's state read once and written once
       (``STATE_BYTES_PER_NEURON`` each way), and its spike read from and
       written to the delay line once, as one bit each way.
    3. Every record written once (``record_bytes``).
    """
    se, si = rec["spikes_exc"], rec["spikes_inh"]
    T, P, NE = se.shape
    NI = si.shape[2]
    exc_sent = se[:max(T - d_exc, 0)].sum(axis=0, dtype=np.int64)  # (P, NE)
    inh_sent = si[:max(T - d_inh, 0)].sum(axis=0, dtype=np.int64)  # (P, NI)
    events = (float((exc_sent * np.roll(deg_ff, -1, axis=0)).sum())
              + float((inh_sent * deg_inh).sum()))
    n_neurons = P * (NE + NI)
    state = 2 * n_neurons * STATE_BYTES_PER_NEURON + 2 * n_neurons / 8
    records = sum(record_bytes(v) for v in rec.values()) / T
    return {
        "syn_events_per_tick": events / T,
        "ops_per_tick": OPS_PER_SYN_EVENT * events / T,
        "bytes_per_tick": WEIGHT_BYTES * events / T + state + records,
    }


def least_time_s(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: operations at the highest
    operation rate in the peak table, or bytes at the HBM rate, whichever
    is longer."""
    op_rate = max(peaks["int8_ops_per_s"], peaks["bf16_flops_per_s"])
    return max(ops / op_rate, nbytes / peaks["hbm_bytes_per_s"])
