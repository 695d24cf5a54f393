"""Served workloads: stimulus-streaming semantics for the fleet engine.

The two scenarios Yan et al. (arXiv:2009.08921) frame as one-user-per-
instance services, rebuilt as *served* graphs:

* **adaptive control** — each user session is a closed PES-learning
  control loop: the session streams its reference signal r(t) in, the
  instance tracks it through the mesh (NEF ensemble -> decoded control ->
  plant -> error back over a graded projection) and streams the plant
  state / tracking error out.  Decoders adapt on-mesh per session — two
  users' instances end up with different weights.
* **keyword spotting (KWS)** — each session streams an audio-like
  waveform (one of ``n_keywords`` synthetic keyword templates) into a
  hybrid NEF -> event-MAC channel farm; the instance streams the MAC
  layer's hidden activations out, and the response summarises them into
  a per-request score vector.

The serving twist over ``repro.learn.adaptive`` / ``repro.chip.workloads``
is WHERE the stimulus lives: instead of a drive table baked into the tick
closure at build time, a served semantics carries the stimulus in the
scan state (``state["stim"]``) — a per-session window of the input
stream (the raw signal plus its int8-MAC s16.15 encoding).  The tick
indexes it with ``t mod window``; the fleet engine replaces the window
between scheduling rounds (host -> device streaming) and a checkpoint of
the carry snapshots the in-flight input with the neuron/learn state.
A plain ``ChipSim.run`` of the same program needs no engine change at
all: ``init_state`` preloads the default stimulus, so a fleet of one is
bit-identical to the unbatched engine — the golden anchor of the tier.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.chip.compile import ChipProgram
from repro.chip.graph import GRADED, NetGraph, Population, Projection
from repro.core.nef import build_ensemble, encode_drive
from repro.kernels.lif.ref import lif_step_ref
from repro.learn.engine import init_learn_state
from repro.learn.rules import PES


def _as_stim(r: np.ndarray, ens) -> dict:
    """A stimulus window: the raw signal + its s16.15 MAC-encoded drive.

    ``encode_drive`` quantizes per time step (per-row int8 scales), so a
    window encoded in segments is bit-identical to the same window
    encoded whole — streamed and preloaded stimuli agree exactly."""
    drive = np.asarray(encode_drive(ens, np.asarray(r, np.float32)[:, None],
                                    use_mac=True))
    return {"r": np.asarray(r, np.float32), "drive": drive}


def blank_stim(ens, n_ticks: int) -> dict:
    """The idle-slot stimulus: silence (and its encoding)."""
    return _as_stim(np.zeros(n_ticks, np.float32), ens)


# -------------------------------------------------------------------------
# Session input streams
# -------------------------------------------------------------------------

@dataclass
class SineStream:
    """One user's input stream: an amp/period/phase sine drawn from the
    session seed (the Yan-et-al. stimulus class, one parameterization per
    user).  ``segment(t0, n)`` returns ticks [t0, t0+n) of the stream as
    a stimulus window — deterministic in (seed, t0, n), so a preempted
    session regenerates exactly the input it would have seen."""
    ens: object
    seed: int
    keyword: Optional[int] = None         # KWS: index into the period table
    periods: tuple = (64.0, 96.0, 144.0, 216.0)
    # control references are SLOW sines (the Yan-et-al. stimulus class —
    # trackable through the loop's 2-tick transport delay); keyword
    # waveforms are fast enough to separate spike patterns per class
    period_range: tuple = (512.0, 2048.0)

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        if self.keyword is None:
            self.amp = float(rng.uniform(0.3, 0.9))
            self.period = float(rng.uniform(*self.period_range))
        else:                              # keyword template + user timbre
            self.amp = float(rng.uniform(0.6, 0.9))
            self.period = float(self.periods[self.keyword
                                             % len(self.periods)])
        self.phase = float(rng.uniform(0.0, self.period))

    def signal(self, t0: int, n: int) -> np.ndarray:
        t = np.arange(t0, t0 + n, dtype=np.float64)
        return (self.amp * np.sin(2 * np.pi * (t + self.phase)
                                  / self.period)).astype(np.float32)

    def segment(self, t0: int, n: int) -> dict:
        return _as_stim(self.signal(t0, n), self.ens)


# -------------------------------------------------------------------------
# Served adaptive control (PES learning per session)
# -------------------------------------------------------------------------

@dataclass
class ServedAdaptiveSemantics:
    """The adaptive-control loop of ``repro.learn.adaptive`` with the
    reference streamed through ``state["stim"]`` instead of baked in.

    All K channels track the session's ONE reference (K redundant
    controllers per user); everything else — decode through the learn
    carry, 1-tick graded transport each way, PES error signals — is the
    AdaptiveControlSemantics tick verbatim."""
    ens: object
    n_channels: int
    default_stim: dict                    # {"r": (L,), "drive": (L, N)}
    plastic: bool = True
    tau_plant_ticks: float = 4.0
    t_sys_s: float = 1e-3
    frozen_decoders: Optional[np.ndarray] = None

    def slot_name(self, k: int) -> str:
        return f"nef{k}->plant{k}"

    def _pe_ids(self, program: ChipProgram):
        nef = np.array([program.pe_slices[f"nef{k}"].start
                        for k in range(self.n_channels)])
        pla = np.array([program.pe_slices[f"plant{k}"].start
                        for k in range(self.n_channels)])
        return nef, pla

    def init_state(self, program: ChipProgram):
        K, N = self.n_channels, self.ens.n_neurons
        st = {"v": jnp.zeros((K, N), jnp.int32),
              "ref": jnp.zeros((K, N), jnp.int32),
              "u_filt": jnp.zeros(K, jnp.float32),
              "u_buf": jnp.zeros(K, jnp.float32),
              "err_buf": jnp.zeros(K, jnp.float32),
              "y": jnp.zeros(K, jnp.float32),
              "stim": {"r": jnp.asarray(self.default_stim["r"]),
                       "drive": jnp.asarray(self.default_stim["drive"])}}
        if self.plastic:
            st["learn"] = init_learn_state(program)
        return st

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        ens = self.ens
        K, N = self.n_channels, ens.n_neurons
        P = program.n_pes
        alpha_syn = float(np.exp(-1.0 / ens.tau_syn_ticks))
        k_p = 1.0 / self.tau_plant_ticks
        nef_np, pla_np = self._pe_ids(program)
        nef_ids, pla_ids = jnp.asarray(nef_np), jnp.asarray(pla_np)
        n_neur = (jnp.zeros(P).at[nef_ids].set(float(N))
                  .at[pla_ids].set(1.0)).astype(jnp.int32)
        if not self.plastic:
            d_frozen = jnp.asarray(
                self.frozen_decoders if self.frozen_decoders is not None
                else np.zeros(N), jnp.float32)

        def tick(state, t):
            stim = state["stim"]
            L = stim["r"].shape[0]        # stimulus window (static shape)
            i = t % L
            dfx = jnp.broadcast_to(stim["drive"][i][None], (K, N))
            v, ref, spk = lif_step_ref(state["v"], state["ref"], dfx,
                                       **ens.lif)
            spk_f = spk.astype(jnp.float32)                   # (K, N)
            n_spk = spk_f.sum(axis=1)                         # (K,)

            if self.plastic:
                d_all = jnp.stack([state["learn"][self.slot_name(k)]
                                   ["w"][:, 0] for k in range(K)])  # (K, N)
            else:
                d_all = jnp.broadcast_to(d_frozen, (K, N))
            contrib = (spk_f * d_all).sum(axis=1)             # (K,)
            u = alpha_syn * state["u_filt"] \
                + (1 - alpha_syn) * contrib * 1000.0

            # plant consumes LAST tick's control (1-tick transport)
            y = state["y"] + (state["u_buf"] - state["y"]) * k_p
            r_now = jnp.broadcast_to(stim["r"][i], (K,))
            e_now = y - r_now
            e_arr = state["err_buf"]     # error arriving at nef this tick

            zP = jnp.zeros(P)
            packets = zP.at[nef_ids].set(1.0).at[pla_ids].set(1.0)
            fifo = zP.at[nef_ids].set(float(N)).at[pla_ids].set(1.0)
            pl = dvfs.select_pl(fifo.astype(jnp.int32))
            snn_ev = zP.at[nef_ids].set(n_spk)
            e_dvfs = em.tick_energy(pl, n_neur, snn_ev, dvfs=True)
            e_pl3 = em.tick_energy(jnp.full((P,), 2), n_neur, snn_ev,
                                   dvfs=False)

            rec = {
                "packets": packets,
                "pl": pl,
                "n_fifo": fifo,
                "syn_events": snn_ev,
                "n_spk": n_spk.sum(),
                "u": u,
                "y": y,
                "r": r_now,
                "track_err": jnp.abs(e_now),
                "dec_norm": jnp.abs(d_all).mean(),
                "e_dvfs_baseline": e_dvfs["baseline"],
                "e_dvfs_neuron": e_dvfs["neuron"],
                "e_dvfs_synapse": e_dvfs["synapse"],
                "e_pl3_baseline": e_pl3["baseline"],
                "e_pl3_neuron": e_pl3["neuron"],
                "e_pl3_synapse": e_pl3["synapse"],
            }
            if self.plastic:
                for k in range(K):
                    name = self.slot_name(k)
                    rec[f"learn/{name}/pre"] = spk_f[k]
                    rec[f"learn/{name}/err"] = e_arr[k][None]

            new_state = {"v": v, "ref": ref, "u_filt": u, "u_buf": u,
                         "err_buf": e_now, "y": y, "stim": stim}
            if self.plastic:
                new_state["learn"] = state["learn"]   # engine advances it
            return new_state, rec

        return tick


def served_adaptive_graph(n_channels: int = 1, n_neurons: int = 64,
                          stim: dict | None = None, stim_len: int = 32,
                          seed: int = 0, learning_rate: float = 3e-6,
                          plastic: bool = True) -> NetGraph:
    """The adaptive-control service graph: same populations/projections
    as ``adaptive_control_graph``, stimulus-streaming semantics.  The
    default stimulus (``stim`` or ``stim_len`` ticks of silence) sizes
    the window every streamed segment must match."""
    ens = build_ensemble(n_neurons, 1, seed=seed)
    stim = stim if stim is not None else blank_stim(ens, stim_len)

    nef_sram = n_neurons * (3 * 4 + 2 * 4) + n_neurons * 4 * 2
    plant_sram = 64
    pops = ([Population(name=f"nef{k}", n=n_neurons, sram_bytes=nef_sram)
             for k in range(n_channels)]
            + [Population(name=f"plant{k}", n=1, sram_bytes=plant_sram)
               for k in range(n_channels)])
    rule = PES(learning_rate=learning_rate) if plastic else None
    projs = ([Projection(src=f"nef{k}", dst=f"plant{k}", payload=GRADED,
                         bits_per_packet=32, delay_ticks=1, plasticity=rule)
              for k in range(n_channels)]
             + [Projection(src=f"plant{k}", dst=f"nef{k}", payload=GRADED,
                           bits_per_packet=32, delay_ticks=1)
                for k in range(n_channels)])
    sem = ServedAdaptiveSemantics(ens=ens, n_channels=n_channels,
                                  default_stim=stim, plastic=plastic)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name=f"served_adaptive{n_channels}"
                         + ("" if plastic else "_frozen"))


# -------------------------------------------------------------------------
# Served keyword spotting (hybrid NEF -> event-MAC farm)
# -------------------------------------------------------------------------

@dataclass
class ServedKwsSemantics:
    """``HybridFarmSemantics`` with the drive streamed per session: all
    K channels of the instance integrate the session's ONE waveform, the
    MAC layer's hidden activations are the streamed response."""
    ens: object
    w_eff: jnp.ndarray                    # (N, hidden) f32 dequantized
    n_pairs: int
    default_stim: dict                    # {"r": (L,), "drive": (L, N)}
    bits_per_spike: int = 16
    t_sys_s: float = 1e-3

    def _pe_ids(self, program: ChipProgram):
        nef = np.array([program.pe_slices[f"nef{k}"].start
                        for k in range(self.n_pairs)])
        mlp = np.array([program.pe_slices[f"mlp{k}"].start
                        for k in range(self.n_pairs)])
        return nef, mlp

    def init_state(self, program: ChipProgram):
        K, N = self.n_pairs, self.ens.n_neurons
        return {"v": jnp.zeros((K, N), jnp.int32),
                "ref": jnp.zeros((K, N), jnp.int32),
                "spike_buf": jnp.zeros((K, N), jnp.float32),
                "stim": {"r": jnp.asarray(self.default_stim["r"]),
                         "drive": jnp.asarray(self.default_stim["drive"])}}

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        from repro.chip.graph import mac_dynamic_energy_j
        ens = self.ens
        K, N, D = self.n_pairs, ens.n_neurons, ens.dims
        P = program.n_pes
        nef_np, mlp_np = self._pe_ids(program)
        nef_ids, mlp_ids = jnp.asarray(nef_np), jnp.asarray(mlp_np)
        n_neur = jnp.zeros(P).at[nef_ids].set(float(N)).astype(jnp.int32)
        w_eff = self.w_eff
        hidden = w_eff.shape[1]

        def tick(state, t):
            stim = state["stim"]
            L = stim["r"].shape[0]
            dfx = jnp.broadcast_to(stim["drive"][t % L][None], (K, N))
            v, ref, spk = lif_step_ref(state["v"], state["ref"], dfx,
                                       **ens.lif)
            spk_f = spk.astype(jnp.float32)                   # (K, N)
            n_spk = spk_f.sum(axis=1)                         # (K,)
            active = (n_spk > 0).astype(jnp.float32)
            bits_out = self.bits_per_spike * n_spk

            arr = state["spike_buf"]                          # (K, N)
            h = jnp.matmul(arr, w_eff, precision="highest")  # (K, hidden)
            n_arr = arr.sum(axis=1)
            mac_events = n_arr * hidden
            bits_in = self.bits_per_spike * n_arr

            zP = jnp.zeros(P)
            packets = zP.at[nef_ids].set(active)
            payload_bits = zP.at[nef_ids].set(bits_out)
            fifo = zP.at[nef_ids].set(float(N)).at[mlp_ids].set(n_arr)
            pl = dvfs.select_pl(fifo.astype(jnp.int32))
            snn_ev = zP.at[nef_ids].set(n_spk * D)
            syn_ev = snn_ev.at[mlp_ids].add(mac_events)
            e_dvfs = em.tick_energy(pl, n_neur, snn_ev, dvfs=True)
            e_pl3 = em.tick_energy(jnp.full((P,), 2), n_neur, snn_ev,
                                   dvfs=False)
            e_mac = zP.at[mlp_ids].set(mac_dynamic_energy_j(mac_events))

            rec = {
                "packets": packets,
                "payload_bits": payload_bits,
                "graded_bits_out": zP.at[nef_ids].set(bits_out),
                "graded_bits_in": zP.at[mlp_ids].set(bits_in),
                "pl": pl,
                "n_fifo": fifo,
                "syn_events": syn_ev,
                "n_spk": n_spk.sum(),
                "hidden_out": h,
                "e_dvfs_baseline": e_dvfs["baseline"],
                "e_dvfs_neuron": e_dvfs["neuron"],
                "e_dvfs_synapse": e_dvfs["synapse"] + e_mac,
                "e_pl3_baseline": e_pl3["baseline"],
                "e_pl3_neuron": e_pl3["neuron"],
                "e_pl3_synapse": e_pl3["synapse"] + e_mac,
            }
            new_state = {"v": v, "ref": ref, "spike_buf": spk_f,
                         "stim": stim}
            return new_state, rec

        return tick


def served_kws_graph(n_pairs: int = 1, n_neurons: int = 64,
                     hidden: int = 16, stim: dict | None = None,
                     stim_len: int = 32, seed: int = 0) -> NetGraph:
    """The KWS service graph: ``hybrid_farm_graph`` populations with
    stimulus-streaming semantics (one user waveform into all channels)."""
    from repro.core.quant import quantize_per_axis
    ens = build_ensemble(n_neurons, 1, seed=seed)
    stim = stim if stim is not None else blank_stim(ens, stim_len)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((n_neurons, hidden)) * 0.1,
                    jnp.float32)
    wq, ws = quantize_per_axis(w, axis=0)
    w_eff = wq.astype(jnp.float32) * ws[None, :]

    nef_sram = n_neurons * (3 * 4 + 2 * 4)
    mlp_sram = n_neurons * hidden + hidden * 4 + n_neurons // 8
    pops = ([Population(name=f"nef{k}", n=n_neurons, sram_bytes=nef_sram)
             for k in range(n_pairs)]
            + [Population(name=f"mlp{k}", n=hidden, sram_bytes=mlp_sram)
               for k in range(n_pairs)])
    projs = [Projection(src=f"nef{k}", dst=f"mlp{k}", payload=GRADED,
                        bits_per_packet=16 * n_neurons, delay_ticks=1)
             for k in range(n_pairs)]
    sem = ServedKwsSemantics(ens=ens, w_eff=w_eff, n_pairs=n_pairs,
                             default_stim=stim)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name=f"served_kws{n_pairs}")


# -------------------------------------------------------------------------
# The scenario catalog the fleet engine serves from
# -------------------------------------------------------------------------

@dataclass
class ServedScenario:
    """Everything the fleet engine needs to serve one workload class:
    how to build the program for a given stimulus window, how to open a
    session's input stream, which per-tick rec keys stream back to the
    user, and how to summarise a finished session into a response."""
    name: str
    ens: object
    build_graph: Callable                 # (stim) -> NetGraph
    make_stream: Callable                 # (seed) -> SineStream
    output_keys: tuple
    response: Callable = None             # ({key: (T, ...) np}) -> dict

    def graph(self, stim_len: int, stim: dict | None = None) -> NetGraph:
        return self.build_graph(stim if stim is not None
                                else blank_stim(self.ens, stim_len))

    def stream(self, seed: int):
        return self.make_stream(seed)


def adaptive_scenario(n_channels: int = 1, n_neurons: int = 64,
                      seed: int = 0, learning_rate: float = 3e-6,
                      plastic: bool = True) -> ServedScenario:
    """Adaptive-control-as-a-service: per-session PES learning."""
    ens = build_ensemble(n_neurons, 1, seed=seed)

    def build(stim):
        return served_adaptive_graph(n_channels, n_neurons, stim=stim,
                                     seed=seed, learning_rate=learning_rate,
                                     plastic=plastic)

    def response(outs: dict) -> dict:
        err = np.asarray(outs["track_err"])         # (T, K)
        tail = max(1, len(err) // 4)
        return {"final_err": float(err[-tail:].max(axis=1).mean()),
                "initial_err": float(err[:tail].max(axis=1).mean())}

    return ServedScenario(
        name=f"adaptive{n_channels}ch", ens=ens, build_graph=build,
        make_stream=lambda seed: SineStream(ens, seed),
        output_keys=("u", "y", "r", "track_err"), response=response)


def kws_scenario(n_pairs: int = 1, n_neurons: int = 64, hidden: int = 16,
                 n_keywords: int = 4, seed: int = 0) -> ServedScenario:
    """Keyword spotting on the hybrid farm: each session streams one of
    ``n_keywords`` waveform templates; the response is the time-mean
    hidden-activation profile (the per-request score vector)."""
    ens = build_ensemble(n_neurons, 1, seed=seed)

    def build(stim):
        return served_kws_graph(n_pairs, n_neurons, hidden, stim=stim,
                                seed=seed)

    def make_stream(session_seed: int):
        kw = int(np.random.default_rng(session_seed).integers(n_keywords))
        return SineStream(ens, session_seed, keyword=kw)

    def response(outs: dict) -> dict:
        h = np.asarray(outs["hidden_out"])          # (T, K, hidden)
        scores = np.abs(h).mean(axis=(0, 1))        # (hidden,)
        return {"scores": scores.round(5).tolist(),
                "top_unit": int(scores.argmax()),
                "spikes": float(np.asarray(outs["n_spk"]).sum())}

    return ServedScenario(
        name=f"kws{n_pairs}ch", ens=ens, build_graph=build,
        make_stream=make_stream, output_keys=("hidden_out", "n_spk"),
        response=response)


SCENARIOS = {"adaptive": adaptive_scenario, "kws": kws_scenario}
