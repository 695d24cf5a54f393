"""jit'd wrappers for the LIF kernel + float<->fixed parameter helpers."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lif.lif import BLOCK_ROWS, LANES, lif_step_pallas
from repro.kernels.explog.ops import fx_exp, to_fx


def lif_params_fx(*, tau_ms: float, v_th: float, v_reset: float,
                  ref_ticks: int, dt_ms: float = 1.0, use_kernel=True,
                  v_min: float | None = None):
    """Fixed-point LIF parameters; alpha from the exp accelerator kernel.

    ``v_min`` is the optional inhibitory-reversal floor (see lif_step_ref)."""
    arg = to_fx(np.float32(-dt_ms / tau_ms))
    alpha = int(fx_exp(arg[None])[0]) if use_kernel else int(
        round(np.exp(-dt_ms / tau_ms) * (1 << 15)))
    return dict(alpha=alpha, v_th=int(to_fx(v_th)), v_reset=int(to_fx(v_reset)),
                ref_ticks=int(ref_ticks),
                v_min=None if v_min is None else int(to_fx(v_min)))


def _pad2d(x):
    n = x.shape[0]
    per = BLOCK_ROWS * LANES
    pad = (-n) % per
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, LANES), n


@functools.partial(jax.jit,
                   static_argnames=("alpha", "v_th", "v_reset", "ref_ticks",
                                    "v_min"))
def lif_step(v, ref_ct, i_syn, *, alpha, v_th, v_reset, ref_ticks,
             v_min=None):
    """v, ref_ct, i_syn: (N,) int32.  Returns (v', ref', spikes) each (N,)."""
    v2, n = _pad2d(v)
    r2, _ = _pad2d(ref_ct)
    i2, _ = _pad2d(i_syn)
    vo, ro, so = lif_step_pallas(v2, r2, i2, alpha=alpha, v_th=v_th,
                                 v_reset=v_reset, ref_ticks=ref_ticks,
                                 v_min=v_min)
    unpad = lambda x: x.reshape(-1)[:n]
    return unpad(vo), unpad(ro), unpad(so)
