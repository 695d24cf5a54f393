"""Closed-loop adaptive control with on-mesh PES learning, plus the
STDP pair demo — the workloads of the plasticity subsystem.

``adaptive_control_graph`` reproduces the control loop Yan et al.
(arXiv:2009.08921) ran on a SpiNNaker 2 prototype with the NEF: a spiking
ensemble encodes the reference signal r(t), its decoded output u drives a
first-order plant y' = (u - y)/tau, and the tracking error e = y - r
closes the loop back to the ensemble, where PES adapts the decoders
online.  On the mesh this is K independent channels of TWO populations
each — ``nef{k}`` (ensemble + decoders) and ``plant{k}`` (plant + error)
— joined by two GRADED projections per channel: the decoded control value
outbound (``plasticity=PES(...)`` — the learned decoders), the error
inbound.  Both values cross real mesh links as graded DNoC packets with a
1-tick transport delay each way, so the loop learns THROUGH the fabric it
will run on; decoders start at zero and the tracking error converges as
PES pulls u toward the plant-inverting control.

All nef populations are laid out before all plant populations (the
hybrid-farm layout), so on a multi-chip board most control loops cross
chip boundaries — the same graph compiles unchanged through
``compile_board`` and trains across the chip-to-chip tier.

``stdp_pair_graph`` is the minimal STDP workload: a Poisson source
population spiking into a LIF population over a plastic SPIKE projection.
Causally effective synapses (pre spikes that precede post spikes)
potentiate, the rest depress — weights live in the engine's learn carry
as s16.15 and move every tick.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.compile import ChipProgram, compile as compile_graph
from repro.chip.graph import GRADED, NetGraph, Population, Projection
from repro.core.nef import build_ensemble, encode_drive
from repro.kernels.explog.ref import FX_ONE
from repro.kernels.lif.ops import lif_params_fx
from repro.kernels.lif.ref import lif_step_ref
from repro.learn.engine import init_learn_state
from repro.learn.rules import PES, STDP


# -------------------------------------------------------------------------
# Adaptive control (PES): K closed loops over the mesh
# -------------------------------------------------------------------------

@dataclass
class AdaptiveControlSemantics:
    """Per-tick step of the K-channel adaptive-control loop.

    States batch the channel axis ((K, N) LIF arrays, one
    ``lif_step_ref`` for the whole farm).  Per channel and tick:

    * nef PE: LIF integrates the MAC-encoded reference drive; the spike
      vector decodes through the CURRENT decoders (read from the learn
      carry), the decoded value low-pass filters into the control u and
      leaves as one 32 b graded packet;
    * plant PE: consumes LAST tick's u, advances y += (u - y)/tau_p,
      emits the error e = y - r back as a graded packet;
    * the error arriving AT the nef PE (one more tick later) is what the
      engine's PES step consumes — reported per slot under
      ``learn/nef{k}->plant{k}/err`` next to the pre spikes.

    With ``plastic=False`` the projections carry no rule and the decode
    uses ``frozen_decoders`` — the frozen twin the learning benchmark
    measures tick-time overhead against.
    """
    ens: object                          # core.nef.Ensemble (shared build)
    drive_fx: jnp.ndarray                # (T, N) s16.15 encode of r(t)
    r_table: np.ndarray                  # (T,) reference signal
    n_channels: int
    plastic: bool = True
    tau_plant_ticks: float = 4.0
    bits_per_value: int = 32
    t_sys_s: float = 1e-3
    frozen_decoders: Optional[np.ndarray] = None   # (N,) used if frozen

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
              "u_buf": jnp.zeros(K, jnp.float32),     # nef -> plant wire
              "err_buf": jnp.zeros(K, jnp.float32),   # plant -> nef wire
              "y": jnp.zeros(K, jnp.float32)}
        if self.plastic:
            st["learn"] = init_learn_state(program)
        return st

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        ens = self.ens
        K, N = self.n_channels, ens.n_neurons
        P = program.n_pes
        drive = self.drive_fx
        r = jnp.asarray(self.r_table, jnp.float32)
        T = drive.shape[0]
        # co-prime phase offsets decorrelate the channels
        offsets = jnp.asarray((np.arange(K) * 31) % T)
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
            tt = (t + offsets) % T
            dfx = drive[tt]                                   # (K, N)
            v, ref, spk = lif_step_ref(state["v"], state["ref"], dfx,
                                       **ens.lif)
            spk_f = spk.astype(jnp.float32)                   # (K, N)
            n_spk = spk_f.sum(axis=1)                         # (K,)

            # decode with the CURRENT decoders (the learn carry is the
            # weight memory; the engine advances it after this tick)
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
            r_now = r[tt]                                     # (K,)
            e_now = y - r_now
            e_arr = state["err_buf"]     # error arriving at nef this tick

            zP = jnp.zeros(P)
            packets = zP.at[nef_ids].set(1.0).at[pla_ids].set(1.0)
            fifo = zP.at[nef_ids].set(float(N)).at[pla_ids].set(1.0)
            pl = dvfs.select_pl(fifo.astype(jnp.int32))
            snn_ev = zP.at[nef_ids].set(n_spk)      # event-based decode
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
                         "err_buf": e_now, "y": y}
            if self.plastic:
                new_state["learn"] = state["learn"]   # engine advances it
            return new_state, rec

        return tick


def adaptive_control_graph(n_channels: int = 4, n_neurons: int = 100,
                           n_ticks: int = 1024, seed: int = 0,
                           learning_rate: float = 3e-6,
                           plastic: bool = True,
                           tau_plant_ticks: float = 4.0,
                           period: int = 2048, amp: float = 0.8) -> NetGraph:
    """K closed adaptive-control loops as one graph (2K populations).

    The reference r(t) is a slow sine (Yan et al.'s stimulus class); its
    MAC-encoded drive table is shared by all channels at co-prime phase
    offsets.  ``plastic=False`` builds the frozen twin (no rules, fixed
    decoders) for overhead baselines."""
    ens = build_ensemble(n_neurons, 1, seed=seed)
    t = np.arange(n_ticks)
    r = amp * np.sin(2 * np.pi * t / period)
    drive_fx = encode_drive(ens, r[:, None], use_mac=True)

    nef_sram = n_neurons * (3 * 4 + 2 * 4) + n_neurons * 4 * 2   # + dec/tr
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
    sem = AdaptiveControlSemantics(
        ens=ens, drive_fx=drive_fx, r_table=r, n_channels=n_channels,
        plastic=plastic, tau_plant_ticks=tau_plant_ticks)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name=f"adaptive_control{n_channels}"
                         + ("" if plastic else "_frozen"))


def convergence_tick(track_err: np.ndarray, threshold: float,
                     window: int) -> int:
    """First tick after which the windowed mean of the worst channel's
    |error| stays below ``threshold`` for good (-1: never converges)."""
    worst = np.asarray(track_err).max(axis=1)            # (T,)
    if len(worst) < window:
        return -1
    kern = np.ones(window) / window
    smooth = np.convolve(worst, kern, mode="valid")      # (T - w + 1,)
    bad = np.flatnonzero(smooth >= threshold)
    if smooth[-1] >= threshold:
        return -1
    if not bad.size:
        return 0                                          # converged at t=0
    return int(bad[-1]) + window                          # in raw ticks


def adaptive_control_workload(n_channels: int = 4, n_neurons: int = 100,
                              n_ticks: int = 2048, board=None,
                              err_threshold: float = 0.1,
                              err_window: int = 64, seed: int = 0,
                              refine: bool = True, **graph_kw) -> dict:
    """Build + compile + run the adaptive-control loop and report
    convergence and the learning-energy share.

    ``board=None`` compiles to a single chip; a ``BoardSpec`` routes the
    SAME graph through ``compile_board`` — the engine and the learning
    carry are identical, only the incidence (and the chip-to-chip tier)
    differ.  ``refine=False`` keeps the greedy graph-order partition
    (all nef populations fill the first chips), so control loops are
    FORCED across chip boundaries — the min-cut refinement would
    otherwise pack each loop's pair onto one chip and zero the cut."""
    graph = adaptive_control_graph(n_channels, n_neurons, n_ticks=n_ticks,
                                   seed=seed, **graph_kw)
    if board is not None:
        from repro.board import compile_board
        prog = compile_board(graph, board, refine=refine)
    else:
        prog = compile_graph(graph)
    sim = ChipSim(prog)
    recs = sim.run(n_ticks)
    track = np.asarray(recs["track_err"])                # (T, K)
    tab = chip_power_table(sim, recs)
    conv = convergence_tick(track, err_threshold, err_window)
    return {
        "sim": sim, "recs": recs, "table": tab, "program": prog,
        "convergence_tick": conv,
        "final_err": float(track[-err_window:].max(axis=1).mean()),
        "initial_err": float(track[:err_window].max(axis=1).mean()),
        "e_learn_j": tab.get("learn", {}).get("energy_j", 0.0),
        "learn_energy_frac": tab.get("learn", {}).get("energy_frac", 0.0),
        "dec_norm": float(np.asarray(recs["dec_norm"])[-1]),
    }


# -------------------------------------------------------------------------
# STDP pair demo: Poisson source -> LIF over a plastic spike projection
# -------------------------------------------------------------------------

@dataclass
class StdpPairSemantics:
    """Pre spikes stream over the mesh (1-tick delay) into a LIF
    population whose fan-in weights the engine's STDP step moves every
    tick.  The forward pass reads the CURRENT weights from the learn
    carry, so potentiation feeds back into excitability — the loop the
    exp-accelerator speedup argument is about."""
    pre_table: np.ndarray                # (T, n_pre) 0/1 spike trains
    n_post: int
    gain: float = 0.55
    lif: dict = field(default_factory=lambda: lif_params_fx(
        tau_ms=10.0, v_th=1.0, v_reset=0.0, ref_ticks=2))
    t_sys_s: float = 1e-3

    def init_state(self, program: ChipProgram):
        n_pre = self.pre_table.shape[1]
        return {"buf": jnp.zeros(n_pre, jnp.float32),
                "v": jnp.zeros(self.n_post, jnp.int32),
                "ref": jnp.zeros(self.n_post, jnp.int32),
                "learn": init_learn_state(program)}

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        table = jnp.asarray(self.pre_table, jnp.float32)
        T, n_pre = table.shape
        n_post = self.n_post
        P = program.n_pes
        pre_pe = program.pe_slices["pre"].start
        post_pe = program.pe_slices["post"].start
        pre_mask = jnp.zeros(P).at[pre_pe].set(1.0)
        post_mask = jnp.zeros(P).at[post_pe].set(1.0)
        n_neur = (post_mask * n_post).astype(jnp.int32)
        gain = self.gain

        def tick(state, t):
            pre_spk = table[t % T]                       # emitted now
            arr = state["buf"]                           # arrived (1-tick)
            w = state["learn"]["pre->post"]["w"]         # (n_pre, n_post)
            w_f = w.astype(jnp.float32) / FX_ONE
            i_syn = jnp.round(jnp.matmul(arr, w_f, precision="highest")
                              * gain * FX_ONE).astype(jnp.int32)
            v, ref, post_spk = lif_step_ref(state["v"], state["ref"],
                                            i_syn, **self.lif)

            n_arr = arr.sum()
            fifo = post_mask * n_arr
            pl = dvfs.select_pl(fifo.astype(jnp.int32))
            syn_ev = post_mask * n_arr * n_post
            e_dvfs = em.tick_energy(pl, n_neur, syn_ev, dvfs=True)
            e_pl3 = em.tick_energy(jnp.full((P,), 2), n_neur, syn_ev,
                                   dvfs=False)
            rec = {
                "packets": pre_mask * pre_spk.sum(),
                "pl": pl,
                "n_fifo": fifo,
                "syn_events": syn_ev,
                "learn/pre->post/pre": arr,
                "learn/pre->post/post": post_spk.astype(jnp.float32),
                "post_spikes": post_spk.sum(),
                "w_mean": w_f.mean(),
                "e_dvfs_baseline": e_dvfs["baseline"],
                "e_dvfs_neuron": e_dvfs["neuron"],
                "e_dvfs_synapse": e_dvfs["synapse"],
                "e_pl3_baseline": e_pl3["baseline"],
                "e_pl3_neuron": e_pl3["neuron"],
                "e_pl3_synapse": e_pl3["synapse"],
            }
            new_state = {"buf": pre_spk, "v": v, "ref": ref,
                         "learn": state["learn"]}
            return new_state, rec

        return tick


def stdp_pair_graph(n_pre: int = 24, n_post: int = 8, n_ticks: int = 512,
                    rate: float = 0.08, seed: int = 0,
                    rule: STDP | None = None) -> NetGraph:
    """Poisson source -> LIF pair with a plastic STDP projection.  Pre
    rates ramp across the population (0.5x .. 1.5x ``rate``), so causally
    effective high-rate synapses separate from the rest."""
    rng = np.random.default_rng(seed)
    rates = rate * np.linspace(0.5, 1.5, n_pre)
    table = (rng.random((n_ticks, n_pre)) < rates[None, :]).astype(
        np.float32)
    rule = rule or STDP()
    pops = [Population(name="pre", n=n_pre, sram_bytes=n_pre * 8),
            Population(name="post", n=n_post,
                       sram_bytes=n_pre * n_post * 4 + n_post * 8)]
    projs = [Projection(src="pre", dst="post", delay_ticks=1,
                        plasticity=rule)]
    sem = StdpPairSemantics(pre_table=table, n_post=n_post)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name="stdp_pair")


def stdp_pair_workload(n_pre: int = 24, n_post: int = 8,
                       n_ticks: int = 512, seed: int = 0,
                       rule: STDP | None = None) -> dict:
    """Compile + run the STDP pair and report weight motion + bounds."""
    graph = stdp_pair_graph(n_pre, n_post, n_ticks=n_ticks, seed=seed,
                            rule=rule)
    prog = compile_graph(graph)
    sim = ChipSim(prog)
    recs = sim.run(n_ticks)
    w_mean = np.asarray(recs["w_mean"])
    tab = chip_power_table(sim, recs)
    return {
        "sim": sim, "recs": recs, "table": tab, "program": prog,
        "w_mean_first": float(w_mean[0]),
        "w_mean_last": float(w_mean[-1]),
        "post_spikes": float(np.asarray(recs["post_spikes"]).sum()),
        "e_learn_j": tab.get("learn", {}).get("energy_j", 0.0),
        "learn_energy_frac": tab.get("learn", {}).get("energy_frac", 0.0),
    }
