"""Neural Engineering Framework ensemble (paper Sec. VI-C, Fig. 19).

The paper's hybrid SNN/DNN showcase, implemented with the same split as the
test chip:

    encode  (vector -> input currents)  = matrix multiply  -> MAC array
    neuron update (spiking LIF)          = SNN path          -> Arm core
    decode  (spikes -> vector)           = event-based adds  -> Arm core

Encoding runs through the int8 MAC GEMM path (core/quant.py) exactly as the
test chip offloads it to the 16x4 array; decoding accumulates decoder rows
only for neurons that spiked ("for spiking neurons, the decoding process is
event based").  A first-order synaptic filter (exp accelerator constant)
smooths the decoded output.

Energy accounting implements BOTH of the paper's synaptic-event metrics:
  * equivalent synops (Braindrop-style): N*N per input spike-equivalent,
  * hardware ops: N*D MACs (encode) + M*D adds (decode), M = spikers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import quantize_per_axis
from repro.kernels.explog.ops import fx_exp, to_fx, from_fx
from repro.kernels.lif.ops import lif_params_fx
from repro.kernels.lif.ref import lif_step_ref
from repro.kernels.mac_gemm.ops import mac_gemm

FX_ONE = 1 << 15


@dataclass
class Ensemble:
    n_neurons: int
    dims: int
    encoders: np.ndarray       # (N, D) float
    gains: np.ndarray          # (N,)
    biases: np.ndarray         # (N,)
    decoders: np.ndarray       # (N, D) float
    lif: dict
    tau_syn_ticks: float = 20.0
    # int8 MAC-path operands
    enc_q: Optional[np.ndarray] = None   # (D, N) int8
    enc_scale: Optional[np.ndarray] = None


def _lif_rate(J, tau_ref=0.002, tau_rc=0.02):
    """Steady-state LIF rate curve used for decoder solving (float)."""
    J = np.maximum(J, 1.0 + 1e-6)
    return 1.0 / (tau_ref + tau_rc * np.log1p(1.0 / (J - 1.0)))


def build_ensemble(n_neurons=512, dims=1, seed=0, tau_ms=20.0,
                   ref_ticks=2) -> Ensemble:
    rng = np.random.default_rng(seed)
    enc = rng.standard_normal((n_neurons, dims))
    enc /= np.linalg.norm(enc, axis=1, keepdims=True)
    # intercepts/max-rates a la Nengo defaults
    intercepts = rng.uniform(-0.9, 0.9, n_neurons)
    max_rates = rng.uniform(200.0, 400.0, n_neurons)
    gains = (1.0 - 1.0 / (1.0 - np.exp((0.002 * max_rates - 1.0)
                                       / (0.02 * max_rates)))) \
        / (intercepts - 1.0)
    biases = 1.0 - gains * intercepts

    # decoder solve on sampled points (regularized least squares)
    xs = np.linspace(-1, 1, 256)[:, None] if dims == 1 else \
        rng.uniform(-1, 1, (512, dims))
    J = gains[None, :] * (xs @ enc.T) + biases[None, :]
    A = np.where(J > 1.0, _lif_rate(J), 0.0)             # (S, N)
    reg = 0.1 * A.max()
    G = A.T @ A + reg**2 * len(xs) * np.eye(n_neurons)
    dec = np.linalg.solve(G, A.T @ xs)                   # (N, D)

    lif = lif_params_fx(tau_ms=tau_ms, v_th=1.0, v_reset=0.0,
                        ref_ticks=ref_ticks)
    enc_w = (gains[:, None] * enc).T                     # (D, N)
    enc_q, enc_scale = quantize_per_axis(jnp.asarray(enc_w, jnp.float32), axis=0)
    return Ensemble(n_neurons, dims, enc, gains, biases, dec, lif,
                    enc_q=np.asarray(enc_q), enc_scale=np.asarray(enc_scale))


def encode_drive(ens: Ensemble, x_seq, *, use_mac=True) -> jnp.ndarray:
    """(T, D) inputs -> (T, N) s16.15 per-tick membrane drive.

    Encoding runs through the int8 MAC array (Fig. 19 left); the result is
    the exact discretization of dv/dt = (J - v)/tau_rc:  v' = a v + (1-a) J.
    Shared by ``run_channel`` and the chip-level hybrid workload
    (``repro.chip.workloads.hybrid_graph``) so both paths stay equivalent.
    """
    xq, x_scale = quantize_per_axis(jnp.asarray(x_seq, jnp.float32), axis=1)
    if use_mac:
        acc = mac_gemm(xq, jnp.asarray(ens.enc_q))       # (T, N) int32
        J = (acc.astype(jnp.float32) * x_scale[:, None]
             * jnp.asarray(ens.enc_scale)[None, :])
    else:
        J = jnp.matmul(jnp.asarray(x_seq, jnp.float32), jnp.asarray(
            ens.gains[:, None] * ens.encoders, jnp.float32).T,
            precision="highest")
    J = J + jnp.asarray(ens.biases, jnp.float32)[None, :]
    alpha = ens.lif["alpha"] / FX_ONE
    return jnp.round(J * (1.0 - alpha) * FX_ONE).astype(jnp.int32)


def run_channel(ens: Ensemble, x_seq: np.ndarray, *, dt_ms=1.0,
                use_mac=True, seed=0):
    """Communication channel: decoded output follows the input vector.

    x_seq: (T, D) inputs in [-1, 1].  Returns dict with xhat (T, D), spike
    counts, and op counts for the energy metrics.  rate_scale converts the
    rate-based current J to per-tick drive (J * dt adds to the s16.15
    membrane).
    """
    T, D = x_seq.shape
    N = ens.n_neurons
    dec = jnp.asarray(ens.decoders, jnp.float32)
    alpha_syn = float(np.exp(-1.0 / ens.tau_syn_ticks))
    drive_fx = encode_drive(ens, x_seq, use_mac=use_mac)

    def tick(state, inp):
        v, ref, xhat = state
        dfx = inp
        v, ref, spk = lif_step_ref(v, ref, dfx, **ens.lif)
        # event-based decode: only spiking neurons contribute (Arm core)
        contrib = jnp.einsum("n,nd->d", spk.astype(jnp.float32), dec,
                             precision="highest")
        # spikes/tick -> rate in Hz (decoders were solved against Hz rates)
        xhat = alpha_syn * xhat + (1 - alpha_syn) * contrib * (1000.0 / dt_ms)
        return (v, ref, xhat), (xhat, spk.sum(), spk)

    v0 = jnp.zeros((N,), jnp.int32)
    r0 = jnp.zeros((N,), jnp.int32)
    x0 = jnp.zeros((D,), jnp.float32)
    _, (xhat, n_spk, spikes) = jax.lax.scan(tick, (v0, r0, x0), drive_fx)
    return {"xhat": np.asarray(xhat), "spikes_per_tick": np.asarray(n_spk),
            "spikes": np.asarray(spikes)}


def synop_metrics(ens: Ensemble, spikes_per_tick: np.ndarray,
                  dyn_energy_per_tick_j: np.ndarray | float) -> dict:
    """The paper's two energy-per-synaptic-event metrics (Sec. VI-C)."""
    N, D = ens.n_neurons, ens.dims
    T = len(spikes_per_tick)
    e = np.broadcast_to(np.asarray(dyn_energy_per_tick_j, np.float64), (T,))
    # equivalent synops: if the NxN matrix were not factorized, each spike
    # causes N synaptic ops
    eq_synops = spikes_per_tick.astype(np.float64) * N
    # hardware ops: N*D MACs (encode) + M*D adds (decode)
    hw_ops = N * D + spikes_per_tick.astype(np.float64) * D
    return {
        "pj_per_eq_synop": float(e.sum() / max(eq_synops.sum(), 1) * 1e12),
        "pj_per_hw_synop": float(e.sum() / max(hw_ops.sum(), 1) * 1e12),
        "mean_rate_hz": float(spikes_per_tick.mean() / N / 1e-3),
    }
