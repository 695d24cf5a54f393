"""Quickstart: the three compute styles of the hybrid PE in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py

1. DNN  — int8 matrix multiply on the MAC-array kernel (MM mode)
2. SNN  — fixed-point LIF neurons with exp-accelerator decay + DVFS
3. hybrid — event-triggered MAC: graded spikes x int8 weights
"""
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core.dvfs import DVFSController
from repro.core.hybrid import event_mac, event_mac_energy_j
from repro.core.quant import quantize_params_linear, quantized_linear
from repro.kernels.explog.ops import fx_exp_float
from repro.kernels.lif.ops import lif_params_fx, lif_step

enable_compilation_cache()

rng = np.random.default_rng(0)

# --- 1. DNN: W8A8 linear layer on the MAC array ---------------------------
x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
wq, ws = quantize_params_linear(w)
y = quantized_linear(x, wq, ws)
err = float(jnp.max(jnp.abs(y - x @ w)) / jnp.max(jnp.abs(x @ w)))
print(f"[DNN]    int8 MAC linear: out {y.shape}, rel err vs f32 = {err:.4f}")

# --- 2. SNN: LIF tick with accelerator-generated decay + DVFS -------------
alpha = fx_exp_float(np.float32(-1.0 / 10.0))   # exp(-dt/tau) on the accel
p = lif_params_fx(tau_ms=10.0, v_th=1.0, v_reset=0.0, ref_ticks=2)
v = jnp.zeros(256, jnp.int32)
ref = jnp.zeros(256, jnp.int32)
drive = jnp.asarray(rng.integers(0, 1 << 14, 256), jnp.int32)
v, ref, spikes = lif_step(v, ref, drive, **p)
pl = int(DVFSController().select_pl(int(spikes.sum())))
print(f"[SNN]    {int(spikes.sum())} spikes this tick -> DVFS selects "
      f"PL{pl + 1} (alpha={float(alpha):.4f})")

# --- 3. hybrid: event-triggered MAC (spikes with graded payloads) ---------
vals = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
active = jnp.asarray(rng.random(32) < 0.25)       # 25% of rows carry events
out, n_ev = event_mac(vals, active, wq, ws)
e_ratio = event_mac_energy_j(int(n_ev), 64, 32) \
    / event_mac_energy_j(32, 64, 32)
print(f"[hybrid] event-MAC: {int(n_ev)}/32 rows dispatched, "
      f"energy = {e_ratio:.2f}x of frame-based")
