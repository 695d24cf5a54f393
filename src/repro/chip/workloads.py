"""Chip-scale workloads as graph builders on the unified API.

Three workload families from the paper, each expressed as a ``NetGraph``
(populations + typed projections + tick semantics), compiled with
``repro.chip.compile.compile`` and executed tick-by-tick by the
workload-agnostic ``ChipSim`` engine:

* ``synfire_graph``  — the Sec. VI-B benchmark: ring of per-PE neuron
  populations, binary spike projections.  The 8-PE graph compiles to a
  program bit-identical to the single-chip ``simulate_synfire``.
* ``dnn_graph``      — feedforward conv layers split into 128 kB-SRAM tile
  populations (Sec. VI-D), graded activation-burst projections.  Frames
  stream through the pipeline tick by tick; tile FIFO occupancy drives
  DVFS, layer completions drive multicast NoC bursts.
* ``hybrid_graph``   — the Sec. II hybrid: a NEF ensemble (SNN path) on
  one QPE spiking into an event-triggered MAC MLP (DNN path) on another,
  the per-tick spike vector crossing the mesh as a graded payload packet.

The ``*_workload`` entry points keep their old signatures but now build /
compile / run through the graph pipeline — no analytic shortcuts.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.compile import ChipProgram, compile as compile_graph
from repro.chip.graph import (GRADED, NetGraph, Population, Projection,
                              busy_window_energy, mac_dynamic_energy_j)
from repro.chip.mapping import synfire_sram_bytes
from repro.chip.mesh_noc import MeshSpec
from repro.chip.stimulus import TableStimulus
from repro.configs import paper
from repro.core.dvfs import DVFSController
from repro.core.hybrid import event_mac_energy_j, event_mac_tick
from repro.core.nef import build_ensemble, encode_drive, synop_metrics
from repro.core.pe import PESpec, partition_layer_to_sram
from repro.core.quant import quantize_params_linear
from repro.core.snn import (build_synfire, make_synfire_tick,
                            synfire_init_state)
from repro.kernels.lif.ref import lif_step_ref


# -------------------------------------------------------------------------
# Synfire ring (SNN)
# -------------------------------------------------------------------------

@dataclass
class SynfireSemantics:
    """Per-tick step of the synfire ring = the single-chip tick function
    (``make_synfire_tick``), unchanged — which is what makes the compiled
    8-PE program bit-identical to ``simulate_synfire``."""
    net: object                        # core.snn.SynfireNet

    def init_state(self, program: ChipProgram):
        return synfire_init_state(self.net)

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        return make_synfire_tick(self.net, dvfs=dvfs, em=em, key=key)

    def make_event_tick(self, program: ChipProgram, *, dvfs, em, key):
        """The activity-compressed synfire tick (``ChipSim`` event mode):
        active sources compact into a bounded index buffer, synaptic
        gather + energy pricing touch only those lanes — bitwise-equal
        records to ``make_tick`` (overflow falls back to dense)."""
        return make_synfire_tick(self.net, dvfs=dvfs, em=em, key=key,
                                 event=True)

    def dvfs_controller(self):
        """The net's own FIFO thresholds (Table II l_th1/l_th2) — picked up
        by ``ChipSim`` when no controller is passed explicitly."""
        sp = self.net.params
        return DVFSController(sp.l_th1, sp.l_th2)

    def build_args(self, program: ChipProgram) -> dict:
        """Args of ``ChipSim``'s ``chip.build`` host span: how the
        synapses are stored (``core.snn.pack_mask``) and their bytes."""
        return {"w_store": "bitmask",
                "synapse_bytes": self.net.synapse_bytes}


def synfire_graph(n_pes: int = 8, seed: int = 0,
                  sp: paper.SynfireParams = paper.SYNFIRE,
                  **build_kw) -> NetGraph:
    """Synfire ring of any length as a graph: one population per PE, spike
    projections around the ring (exc -> next PE's exc+inh; the same-PE
    inhibitory loop stays inside the population's tick)."""
    net = build_synfire(seed, n_pes=n_pes, sp=sp, **build_kw)
    sram = synfire_sram_bytes(net.params)
    pops = [Population(name=f"pe{i}", n=net.params.neurons_per_core,
                       sram_bytes=sram) for i in range(n_pes)]
    projs = [Projection(src=f"pe{i}", dst=f"pe{(i + 1) % n_pes}",
                        delay_ticks=int(net.params.delay_exc_ms))
             for i in range(n_pes)]
    return NetGraph(populations=pops, projections=projs,
                    semantics=SynfireSemantics(net), name=f"synfire{n_pes}")


def synfire_workload(n_pes: int = 8, mesh: MeshSpec | None = None,
                     n_ticks: int = 1200, seed: int = 0) -> dict:
    """Build, compile, run and account a synfire ring on the mesh."""
    graph = synfire_graph(n_pes, seed=seed)
    sim = ChipSim(compile_graph(graph, mesh))
    recs = sim.run(n_ticks)
    return {"sim": sim, "recs": recs, "table": chip_power_table(sim, recs)}


# -------------------------------------------------------------------------
# Tiled DNN (feedforward pipeline)
# -------------------------------------------------------------------------

# A small VGG-ish feedforward stack (the paper's Sec. VI-D keyword-spotting
# class of networks): enough layers to spread over tens of PEs.
DEFAULT_DNN = [
    dict(name="conv1", h=32, w=32, cin=3, cout=32, kh=3, kw=3),
    dict(name="conv2", h=32, w=32, cin=32, cout=32, kh=3, kw=3),
    dict(name="conv3", h=16, w=16, cin=32, cout=64, kh=3, kw=3),
    dict(name="conv4", h=16, w=16, cin=64, cout=64, kh=3, kw=3),
]


def dnn_graph(layers=None, pe: PESpec = PESpec(),
              bytes_per: int = 1) -> NetGraph:
    """Feedforward conv stack as a graph: one population per layer, tiled
    to the 128 kB SRAM; graded projections carry each tile's activation
    burst (its share of the layer's output) to every next-layer tile."""
    layers = layers or DEFAULT_DNN
    pops, projs = [], []
    for li, ly in enumerate(layers):
        rows, cout_t, n_tiles = partition_layer_to_sram(
            pe, ly["h"], ly["w"], ly["cin"], ly["cout"], ly["kh"], ly["kw"],
            bytes_per=bytes_per)
        in_b = (rows + ly["kh"] - 1) * ly["w"] * ly["cin"] * bytes_per
        w_b = ly["kh"] * ly["kw"] * ly["cin"] * cout_t * bytes_per
        out_b = rows * ly["w"] * cout_t * 4
        name = ly.get("name", f"layer{li}")
        out_bytes = ly["h"] * ly["w"] * ly["cout"] * bytes_per
        macs = ly["h"] * ly["w"] * ly["cout"] * ly["cin"] * ly["kh"] * ly["kw"]
        pops.append(Population(
            name=name, n=out_bytes, sram_bytes=in_b + w_b + out_b,
            n_tiles=n_tiles,
            meta=dict(
                ly, rows_per_tile=rows, cout_per_tile=cout_t,
                cycles_per_tile=pe.mac_conv_cycles(
                    min(rows, ly["h"]), ly["w"], ly["cin"], cout_t,
                    ly["kh"], ly["kw"]),
                macs_per_tile=macs / n_tiles,
                in_events=(ly["h"] * ly["w"] * ly["cin"] if li == 0
                           else pops[-1].n),
                out_bytes=out_bytes)))
        if li:
            prev = pops[-2]
            projs.append(Projection(
                src=prev.name, dst=name, payload=GRADED,
                bits_per_packet=-(-prev.meta["out_bytes"] * 8
                                  // prev.n_tiles)))
    g = NetGraph(populations=pops, projections=projs, name="tiled_dnn")
    g.semantics = DnnPipelineSemantics(graph=g)
    return g


@dataclass
class DnnPipelineSemantics:
    """Tick-by-tick streaming inference over the tiled layer pipeline.

    Frames are injected into the first layer every ``frame_interval``
    ticks.  A tile queues arriving frames in its FIFO (occupancy drives
    DVFS, exactly as spike counts do for the SNN), processes one frame for
    ``stage_ticks`` ticks at PL3, and on completion the layer multicasts
    one graded activation burst per tile to every next-layer tile (1-tick
    NoC transport delay).  Energy: Eq. (1) baseline from the busy window
    plus MAC-array dynamic energy per dispatched op — activity-driven on
    both the datapath and the NoC.
    """
    graph: NetGraph
    n_frames: int = 4
    frame_interval: int = 0            # 0 -> auto: slowest stage (pipeline rate)
    t_sys_s: float = 1e-3

    def static_tables(self, program: ChipProgram) -> dict:
        """Placement-derived per-PE tables (stage latencies, layer
        membership, event counts).  Memoized per program: ``make_tick``
        and the workload report share one computation."""
        cache = self.__dict__.setdefault("_tables", {})
        key = id(program)
        if key not in cache:
            cache[key] = self._build_tables(program)
        return cache[key]

    def _build_tables(self, program: ChipProgram):
        pops = self.graph.populations
        P = program.n_pes
        n_layers = len(pops)
        pl3_cycles = paper.PERF_LEVELS[2].freq_hz * self.t_sys_s
        stage_ticks = np.array(
            [max(1, int(np.ceil(p.meta["cycles_per_tile"] / pl3_cycles)))
             for p in pops], np.int32)
        member = np.zeros((n_layers, P), np.float32)
        stage_pe = np.zeros(P, np.int32)
        macs_tick = np.zeros(P, np.float32)
        cycles_tick = np.zeros(P, np.float32)
        in_events = np.zeros(P, np.int32)
        for li, p in enumerate(pops):
            sl = program.pe_slices[p.name]
            member[li, sl] = 1.0
            stage_pe[sl] = stage_ticks[li]
            macs_tick[sl] = p.meta["macs_per_tile"] / stage_ticks[li]
            cycles_tick[sl] = p.meta["cycles_per_tile"] / stage_ticks[li]
            in_events[sl] = p.meta["in_events"]
        tiles_per_layer = member.sum(axis=1)
        # emission: layer l done -> 1 frame arrives at every tile of l+1
        nxt = np.zeros((n_layers, P), np.float32)
        for li in range(n_layers - 1):
            nxt[li, program.pe_slices[pops[li + 1].name]] = 1.0
        emit_mask = (member[:-1].sum(axis=0) > 0).astype(np.float32) \
            if n_layers > 1 else np.zeros(P, np.float32)
        first_mask = member[0]
        interval = self.frame_interval or int(stage_ticks.max() + 1)
        return dict(member=member, tiles=tiles_per_layer, nxt=nxt,
                    stage_pe=stage_pe, macs_tick=macs_tick,
                    cycles_tick=cycles_tick, in_events=in_events,
                    emit_mask=emit_mask, first_mask=first_mask,
                    interval=interval, stage_ticks=stage_ticks)

    def init_state(self, program: ChipProgram):
        P = program.n_pes
        return {"fifo": jnp.zeros(P, jnp.int32),
                "remaining": jnp.zeros(P, jnp.int32),
                "buf": jnp.zeros(P, jnp.float32)}

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        st = self.static_tables(program)
        member = jnp.asarray(st["member"])
        tiles = jnp.asarray(st["tiles"])
        nxt = jnp.asarray(st["nxt"])
        stage_pe = jnp.asarray(st["stage_pe"])
        macs_tick = jnp.asarray(st["macs_tick"])
        cycles_tick = jnp.asarray(st["cycles_tick"])
        in_events = jnp.asarray(st["in_events"])
        emit_mask = jnp.asarray(st["emit_mask"])
        first_mask = jnp.asarray(st["first_mask"])
        interval = st["interval"]
        n_frames = self.n_frames
        tops_pl3 = paper.MAC_TOPS_PER_W[(paper.HIGH_VDD, paper.HIGH_FREQ)]

        def tick(state, t):
            inject = ((t % interval) == 0) & (t < n_frames * interval)
            arr = state["buf"] + inject.astype(jnp.float32) * first_mask
            arr_i = arr.astype(jnp.int32)
            fifo = state["fifo"] + arr_i
            n_fifo = arr_i * in_events                 # events entering FIFO
            pl_arr = dvfs.select_pl(n_fifo)

            start = (state["remaining"] == 0) & (fifo > 0)
            fifo = fifo - start.astype(jnp.int32)
            remaining = state["remaining"] + start * stage_pe
            busy = remaining > 0
            pl = jnp.maximum(pl_arr, busy.astype(jnp.int32) * 2)
            remaining = remaining - busy.astype(jnp.int32)
            done = busy & (remaining == 0)

            done_f = done.astype(jnp.float32)
            layer_done = (jnp.matmul(member, done_f, precision="highest")
                          >= tiles).astype(jnp.float32)
            packets = done_f * emit_mask               # activation bursts
            buf = jnp.matmul(layer_done, nxt,          # arrives next tick
                             precision="highest")

            macs = busy.astype(jnp.float32) * macs_tick
            cycles = busy.astype(jnp.float32) * cycles_tick
            e_mac = mac_dynamic_energy_j(macs)
            e_mac_pl3 = mac_dynamic_energy_j(macs, tops_per_w=tops_pl3)
            zeros = jnp.zeros_like(e_mac)
            rec = {
                "packets": packets,
                "pl": pl,
                "n_fifo": n_fifo,
                "syn_events": macs,
                "busy": busy,
                "layer_done": layer_done,
                "frame_out": layer_done[-1],
                "e_dvfs_baseline": busy_window_energy(
                    pl, cycles, t_sys_s=self.t_sys_s, dvfs=True),
                "e_dvfs_neuron": zeros,
                "e_dvfs_synapse": e_mac,
                "e_pl3_baseline": busy_window_energy(
                    jnp.full_like(pl, 2), cycles, t_sys_s=self.t_sys_s,
                    dvfs=False),
                "e_pl3_neuron": zeros,
                "e_pl3_synapse": e_mac_pl3,
            }
            new_state = {"fifo": fifo, "remaining": remaining, "buf": buf}
            return new_state, rec

        return tick


def tiled_dnn_workload(layers=None, mesh: MeshSpec | None = None,
                       pe: PESpec = PESpec(), n_frames: int = 4,
                       n_ticks: int | None = None) -> dict:
    """Map a feedforward stack over the mesh and STREAM frames through it.

    Unlike the old analytic table, the compiled program executes tick by
    tick on ``ChipSim``: tiles process when their FIFO holds a frame,
    completions multicast graded activation bursts over real mesh links,
    and the DVFS/NoC accounting falls out of the per-tick records.
    """
    layers = layers or DEFAULT_DNN
    graph = dnn_graph(layers, pe=pe)
    graph.semantics.n_frames = n_frames
    prog = compile_graph(graph, mesh, pe=pe)
    sim = ChipSim(prog)

    st = graph.semantics.static_tables(prog)
    pipeline_ticks = int(st["stage_ticks"].sum() + len(layers))
    if n_ticks is None:
        n_ticks = st["interval"] * n_frames + pipeline_ticks + 4
    recs = sim.run(n_ticks)

    frame_out = np.asarray(recs["frame_out"])
    out_ticks = np.flatnonzero(frame_out > 0)
    latency_s = (float(out_ticks[0] + 1) * graph.semantics.t_sys_s
                 if out_ticks.size else float("nan"))
    loads = np.asarray(recs["link_load"])              # (T, L)
    flits = np.asarray(recs["link_flits"])
    per_layer = []
    for pop, ticks in zip(graph.populations, st["stage_ticks"]):
        per_layer.append({
            "name": pop.name, "n_tiles": pop.n_tiles,
            "rows_per_tile": pop.meta["rows_per_tile"],
            "cout_per_tile": pop.meta["cout_per_tile"],
            "cycles_per_tile": pop.meta["cycles_per_tile"],
            "stage_ticks": int(ticks),
            "layer_latency_s": float(ticks) * graph.semantics.t_sys_s,
        })
    compute_s = sum(l["layer_latency_s"] for l in per_layer)
    tab = chip_power_table(sim, recs)
    return {
        "sim": sim, "recs": recs, "table": tab,
        "layers": per_layer,
        "n_pes_used": prog.n_pes,
        "mesh": (prog.mesh.width, prog.mesh.height),
        "n_frames_out": int(frame_out.sum()),
        "latency_s": latency_s,
        "compute_s": compute_s,
        "noc_s": prog.worst_tree_hops * prog.noc.spec.hop_cycles
                 / prog.noc.spec.freq_hz,
        "energy_mac_j": float(np.asarray(recs["e_dvfs_synapse"]).sum()),
        "energy_noc_j": float(np.asarray(recs["e_noc"]).sum()),
        "link_loads": loads,
        "peak_link_load": float(loads.max()) if loads.size else 0.0,
        "peak_link_flits": float(flits.max()) if flits.size else 0.0,
    }


# -------------------------------------------------------------------------
# Hybrid NEF + event-MAC MLP
# -------------------------------------------------------------------------

@dataclass
class HybridSemantics:
    """NEF ensemble (SNN path) on one QPE, event-triggered MAC MLP (DNN
    path) on another, executing tick by tick ON the mesh (Sec. II).

    Per tick: the ensemble's LIF neurons integrate the (MAC-encoded) drive;
    spiking neurons are decoded event-based into ``xhat``; the spike vector
    crosses the mesh as ONE graded-payload packet (16 b per spike) and is
    consumed by the MLP PE on the NEXT tick, where only arrived events
    dispatch weight rows to the MAC array.  Ticks with no spikes send
    nothing and multiply nothing — energy follows activity on the NoC and
    in the datapath alike.

    It stays apart from ``HybridFarmSemantics``: it decodes ``xhat`` and
    records ``spikes`` and ``n_dispatched``, which the farm at 768 x 512
    must not compute, and a shared tick would branch on its caller.
    """
    ens: object                         # core.nef.Ensemble
    wq: jnp.ndarray                     # (N, hidden) int8
    w_scale: jnp.ndarray
    drive_fx: jnp.ndarray               # (T, N) int32 s16.15 encode drive
    bits_per_spike: int = 16
    t_sys_s: float = 1e-3

    def init_state(self, program: ChipProgram):
        N = self.ens.n_neurons
        return {"v": jnp.zeros(N, jnp.int32),
                "ref": jnp.zeros(N, jnp.int32),
                "xhat": jnp.zeros(self.ens.dims, jnp.float32),
                "spike_buf": jnp.zeros(N, jnp.float32)}

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        ens = self.ens
        N, D = ens.n_neurons, ens.dims
        hidden = self.wq.shape[1]
        dec = jnp.asarray(ens.decoders, jnp.float32)
        w_eff = self.wq.astype(jnp.float32) * self.w_scale[None, :]
        alpha_syn = float(np.exp(-1.0 / ens.tau_syn_ticks))
        drive = self.drive_fx
        T = drive.shape[0]
        P = program.n_pes
        src = program.pe_slices["nef"].start
        dst = program.pe_slices["mlp"].start
        nef_mask = jnp.zeros(P).at[src].set(1.0)
        mlp_mask = jnp.zeros(P).at[dst].set(1.0)
        n_neur = (nef_mask * N).astype(jnp.int32)

        def tick(state, t):
            dfx = drive[t % T]
            v, ref, spk = lif_step_ref(state["v"], state["ref"], dfx,
                                       **ens.lif)
            spk_f = spk.astype(jnp.float32)
            n_spk = spk_f.sum().astype(jnp.int32)
            # event-based decode on the Arm core (only spikers contribute)
            contrib = jnp.matmul(spk_f, dec, precision="highest")
            # spikes/tick -> rate in Hz (decoders were solved against Hz
            # rates) — same discretization as core.nef.run_channel
            xhat = (alpha_syn * state["xhat"]
                    + (1 - alpha_syn) * contrib * 1000.0)

            # NoC: one graded packet iff the tick had spikes
            active = (n_spk > 0).astype(jnp.float32)
            packets = nef_mask * active
            bits_out = self.bits_per_spike * n_spk
            payload_bits = nef_mask * bits_out.astype(jnp.float32)

            # MLP PE consumes LAST tick's spike vector (1-tick transport)
            arr = state["spike_buf"]
            h, n_arr = event_mac_tick(arr, w_eff)
            mac_events = n_arr * hidden
            bits_in = self.bits_per_spike * n_arr

            # DVFS: inbound event counts pick the PL on both PEs
            fifo = (nef_mask * N + mlp_mask * n_arr.astype(jnp.float32))
            pl = dvfs.select_pl(fifo.astype(jnp.int32))
            # Arm-core synaptic events (decode adds) price via Eq. (1);
            # the MLP's MAC-array ops price via TOPS/W ONLY — charging
            # them e_synapse_j too would double-count the datapath
            snn_ev = nef_mask * n_spk.astype(jnp.float32) * D
            syn_ev = snn_ev + mlp_mask * mac_events.astype(jnp.float32)
            e_dvfs = em.tick_energy(pl, n_neur, snn_ev, dvfs=True)
            e_pl3 = em.tick_energy(jnp.full((P,), 2), n_neur, snn_ev,
                                   dvfs=False)
            e_mac = mac_dynamic_energy_j(mac_events.astype(jnp.float32))

            rec = {
                "packets": packets,
                "payload_bits": payload_bits,
                "graded_bits_out": nef_mask * bits_out.astype(jnp.float32),
                "graded_bits_in": mlp_mask * bits_in.astype(jnp.float32),
                "pl": pl,
                "n_fifo": fifo,
                "syn_events": syn_ev,
                "spikes": spk.astype(jnp.int8),
                "n_spk": n_spk,
                "n_dispatched": (n_arr > 0).astype(jnp.int32),
                "mac_events": mac_events,
                "xhat": xhat,
                "hidden_out": h,
                "e_dvfs_baseline": e_dvfs["baseline"],
                "e_dvfs_neuron": e_dvfs["neuron"],
                "e_dvfs_synapse": e_dvfs["synapse"] + mlp_mask * e_mac,
                "e_pl3_baseline": e_pl3["baseline"],
                "e_pl3_neuron": e_pl3["neuron"],
                "e_pl3_synapse": e_pl3["synapse"] + mlp_mask * e_mac,
            }
            new_state = {"v": v, "ref": ref, "xhat": xhat,
                         "spike_buf": spk_f}
            return new_state, rec

        return tick


def hybrid_graph(n_neurons: int = 256, hidden: int = 64,
                 n_ticks: int = 600, seed: int = 0) -> NetGraph:
    """NEF ensemble + event-MAC MLP as a two-population graph with a
    graded projection (16 b per spike event) between separate QPEs."""
    ens = build_ensemble(n_neurons, 1, seed=seed)

    # drive the channel with a slow sine (Fig. 20's stimulus class),
    # MAC-encoded by the SAME helper run_channel uses — the on-mesh hybrid
    # and the single-PE NEF path integrate identical per-tick drive
    t = np.arange(n_ticks)
    x = 0.8 * np.sin(2 * np.pi * t / 400)[:, None]
    drive_fx = encode_drive(ens, x, use_mac=True)

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((n_neurons, hidden)) * 0.1,
                    jnp.float32)
    wq, ws = quantize_params_linear(w)

    nef_sram = n_neurons * (3 * 4 + 2 * 4) + n_neurons * 1 * 4 * 2
    mlp_sram = n_neurons * hidden + hidden * 4 + n_neurons // 8
    pops = [
        Population(name="nef", n=n_neurons, sram_bytes=nef_sram,
                   align_qpe=True, meta={"x": x}),
        Population(name="mlp", n=hidden, sram_bytes=mlp_sram,
                   align_qpe=True),
    ]
    projs = [Projection(src="nef", dst="mlp", payload=GRADED,
                        bits_per_packet=16 * n_neurons, delay_ticks=1)]
    sem = HybridSemantics(ens=ens, wq=wq, w_scale=ws, drive_fx=drive_fx)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name="hybrid_nef_mlp")


def check_farm_placement(nef_ids: np.ndarray, mlp_ids: np.ndarray,
                         n_pes: int) -> None:
    """Raise ``ValueError`` unless channel k's NEF PE is k and its MLP
    PE is K + k, on P == 2K PEs.  Every compile of ``hybrid_farm_graph``
    lays a farm out so: populations take logical PEs in graph order
    (``chip.compile``, ``board.route.place_partition``; refinement and
    routeopt move only coordinates), and the graph lists nef0..nef{K-1},
    then mlp0..mlp{K-1}, one tile each.  The farm's per-PE rows are then
    the concatenation of its (K,) NEF and (K,) MLP values."""
    K = len(nef_ids)
    if not (n_pes == 2 * K and np.array_equal(nef_ids, np.arange(K))
            and np.array_equal(mlp_ids, K + np.arange(K))):
        raise ValueError(
            f"hybrid farm of {K} channels on {n_pes} PEs: expected NEF "
            f"PEs 0..{K - 1} and MLP PEs {K}..{2 * K - 1}, the layout "
            "every compile of hybrid_farm_graph gives")


@dataclass
class HybridFarmSemantics:
    """K independent NEF -> event-MAC channels ticking in lockstep — the
    Sec. II hybrid at board scale (one channel = ``HybridSemantics``).

    All channels share one ensemble build (weights, LIF constants) and
    read their drive from ``stimulus`` (``repro.chip.stimulus``).  The
    benchmarked farm reads a table, channel k ``k * phase_step`` ticks
    ahead, so spike times — and therefore NoC traffic — decorrelate
    across the mesh; the served farm (keyword spotting) streams one
    session's waveform into every channel.
    States batch the channel axis: (K, N) arrays, one ``lif_step_ref``
    call for the whole farm.  Each NEF PE emits at most one graded
    spike-vector packet per tick (16 b per spike), consumed by its paired
    MLP PE on the next tick; energy follows activity on the NoC and in
    the datapath, exactly as in the single-channel semantics.

    The tick's stages carry the synfire tick's names where they mean the
    same: ``background`` (the drive lookup), ``neuron`` (LIF),
    ``synapse`` (the event-MAC GEMM) and ``route`` (per-PE placement,
    energies, records).
    """
    ens: object                         # core.nef.Ensemble (shared build)
    w_eff: jnp.ndarray                  # (N, hidden) f32 dequantized
    stimulus: object                    # chip.stimulus source of the drive
    n_pairs: int
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
                **self.stimulus.init_state()}

    def build_args(self, program: ChipProgram) -> dict:
        """Args of ``ChipSim``'s ``chip.build`` host span: the farm's
        widths, and the board and link count of each NoC tier it was
        compiled onto."""
        args = {"n_pairs": self.n_pairs, "n_neurons": self.ens.n_neurons,
                "hidden": int(self.w_eff.shape[1])}
        board = getattr(program, "board", None)
        if board is not None:
            args["board"] = (f"{board.chips_x}x{board.chips_y} chips of "
                             f"{board.chip.width}x{board.chip.height} QPEs")
        for tier, mask in program.noc.tier_masks().items():
            args[f"links_{tier}"] = int(np.count_nonzero(mask))
        return args

    def make_tick(self, program: ChipProgram, *, dvfs, em, key):
        ens = self.ens
        K, N, D = self.n_pairs, ens.n_neurons, ens.dims
        hidden = self.w_eff.shape[1]
        P = program.n_pes
        drive_at, _ = self.stimulus.reader(K)
        nef_np, mlp_np = self._pe_ids(program)
        check_farm_placement(nef_np, mlp_np, P)
        n_neur = jnp.concatenate([jnp.full(K, N, jnp.int32),
                                  jnp.zeros(K, jnp.int32)])
        w_eff = self.w_eff

        def place2(nef_vals, mlp_vals):
            """(K,) nef values + (K,) mlp values -> (P,) per-PE row."""
            return jnp.concatenate([nef_vals, mlp_vals])

        def tick(state, t):
            with jax.named_scope("background"):
                dfx = drive_at(state, t)                      # (K, N)
            with jax.named_scope("neuron"):
                v, ref, spk = lif_step_ref(state["v"], state["ref"], dfx,
                                           **ens.lif)
                spk_f = spk.astype(jnp.float32)               # (K, N)
                n_spk = spk_f.sum(axis=1)                     # (K,)
                active = (n_spk > 0).astype(jnp.float32)
                bits_out = self.bits_per_spike * n_spk

            # MLP PEs consume LAST tick's spike vectors (1-tick transport)
            with jax.named_scope("synapse"):
                arr = state["spike_buf"]                      # (K, N)
                h = jnp.matmul(arr, w_eff,
                               precision="highest")           # (K, hidden)
                n_arr = arr.sum(axis=1)                       # (K,)
                mac_events = n_arr * hidden
                bits_in = self.bits_per_spike * n_arr

            with jax.named_scope("route"):
                zk = jnp.zeros(K, jnp.float32)
                packets = place2(active, zk)
                payload_bits = place2(bits_out, zk)
                fifo = place2(jnp.full(K, float(N)), n_arr)
                pl = dvfs.select_pl(fifo.astype(jnp.int32))
                snn_ev = place2(n_spk * D, zk)
                syn_ev = place2(n_spk * D, mac_events)
                e_dvfs = em.tick_energy(pl, n_neur, snn_ev, dvfs=True)
                e_pl3 = em.tick_energy(jnp.full((P,), 2), n_neur, snn_ev,
                                       dvfs=False)
                e_mac = place2(zk, mac_dynamic_energy_j(mac_events))

                rec = {
                    "packets": packets,
                    "payload_bits": payload_bits,
                    "graded_bits_out": place2(bits_out, zk),
                    "graded_bits_in": place2(zk, bits_in),
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
                         **self.stimulus.carry(state)}
            return new_state, rec

        return tick


def hybrid_farm_graph(n_pairs: int, n_neurons: int = 32, hidden: int = 16,
                      table_ticks: int = 256, seed: int = 0,
                      amplitude: float = 0.8, period_ticks: float = 97,
                      phase_step_ticks: int = 17,
                      stimulus=None) -> NetGraph:
    """``n_pairs`` independent NEF -> event-MAC channels as one graph
    (2 * n_pairs populations).  All NEF populations are laid out before
    all MLP populations, so channel k's projection crosses a long stretch
    of the snake — board-scale multicast traffic over real mesh links.

    The drive is a sine of ``amplitude`` and ``period_ticks``, tabled
    over ``table_ticks`` ticks (the table wraps; a whole number of
    periods wraps without a jump); channel k reads it
    ``k * phase_step_ticks`` ticks ahead.  A ``stimulus``
    (``repro.chip.stimulus``) replaces that table: the served farm
    streams each session's waveform.
    """
    ens = build_ensemble(n_neurons, 1, seed=seed)
    if stimulus is None:
        stimulus = TableStimulus.sine(ens, table_ticks, amplitude,
                                      period_ticks, phase_step_ticks)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((n_neurons, hidden)) * 0.1,
                    jnp.float32)
    wq, ws = quantize_params_linear(w)
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
    sem = HybridFarmSemantics(ens=ens, w_eff=w_eff, stimulus=stimulus,
                              n_pairs=n_pairs)
    return NetGraph(populations=pops, projections=projs, semantics=sem,
                    name=f"hybrid_farm{n_pairs}")


# -------------------------------------------------------------------------
# Board-scale variants: the same three workload classes sized to fill a
# multi-chip board and compiled across chip boundaries
# -------------------------------------------------------------------------

def synfire_board_graph(board, fill: float = 1.0, seed: int = 0,
                        sp: paper.SynfireParams = paper.SYNFIRE,
                        **build_kw) -> NetGraph:
    """Synfire ring sized to ``fill`` of a board's PEs — one population
    per PE, so the ring snakes through every chip and the wrap-around
    edge crosses the whole chip grid."""
    return synfire_graph(n_pes=max(2, int(board.n_pes * fill)), seed=seed,
                         sp=sp, **build_kw)


def dnn_board_graph(board, layer: dict | None = None,
                    pe: PESpec = PESpec(), bytes_per: int = 1) -> NetGraph:
    """Feedforward conv pipeline sized to a board: the template ``layer``
    (default: the chip_scale 64x64x32->64 conv, ~13 tiles under the
    128 kB SRAM) repeats until the tiled stack fills the board's PEs, so
    consecutive layers land on neighboring chips and every inter-layer
    activation burst that crosses a boundary rides a chip-to-chip link."""
    layer = layer or dict(h=64, w=64, cin=32, cout=64, kh=3, kw=3)
    _, _, tiles = partition_layer_to_sram(
        pe, layer["h"], layer["w"], layer["cin"], layer["cout"],
        layer["kh"], layer["kw"], bytes_per=bytes_per)
    # populations are atomic on a chip, so size by whole layers per chip
    # (the partitioner cannot split a layer across a chip boundary)
    n_layers = max(2, (board.chip.n_pes // tiles) * board.n_chips)
    return dnn_graph([dict(layer, name=f"conv{i}") for i in range(n_layers)],
                     pe=pe, bytes_per=bytes_per)


def hybrid_farm_board_graph(board, n_neurons: int = 32, hidden: int = 16,
                            table_ticks: int = 256, seed: int = 0,
                            chip: str = "2x2", **drive) -> NetGraph:
    """Hybrid NEF -> event-MAC farm sized to a board: one channel per PE
    pair.  All NEF populations precede all MLP populations, so after
    partitioning most channels span chips — worst-case (traffic-heavy)
    layout for the chip-to-chip tier, which is what makes it the board
    benchmark's headline workload.

    ``board`` is a ``BoardSpec`` or its ``BoardSpec.parse`` text ("4x12",
    of ``chip`` chips); ``drive`` holds ``hybrid_farm_graph``'s drive
    arguments (``amplitude``, ``period_ticks``, ``phase_step_ticks``)."""
    from repro.board import BoardSpec
    if isinstance(board, str):
        board = BoardSpec.parse(board, chip=chip)
    graph = hybrid_farm_graph(n_pairs=max(1, board.n_pes // 2),
                              n_neurons=n_neurons, hidden=hidden,
                              table_ticks=table_ticks, seed=seed, **drive)
    graph.board = board
    return graph


def board_workload(graph: NetGraph, board, n_ticks: int = 64,
                   refine: bool = True, **sim_kw) -> dict:
    """Partition + compile ``graph`` across ``board``, run it on the
    unchanged engine, and report the per-tier traffic split."""
    from repro.board import compile_board
    prog = compile_board(graph, board, refine=refine)
    sim = ChipSim(prog, **sim_kw)
    recs = sim.run(n_ticks)
    flits = np.asarray(recs["link_flits"])
    x_flits = float(np.asarray(recs["flits_xchip"]).sum()) \
        if "flits_xchip" in recs else 0.0
    tot = float(flits.sum())
    return {
        "sim": sim, "recs": recs, "table": chip_power_table(sim, recs),
        "program": prog,
        "n_chips_used": int((prog.part.chips_of_graph() > 0).sum()),
        "cut_flits": prog.part.cut_flits,
        "flits_total": tot,
        "flits_xchip": x_flits,
        "xchip_frac": x_flits / tot if tot else 0.0,
        "energy_noc_j": float(np.asarray(recs["e_noc"]).sum()),
        "energy_xchip_j": float(np.asarray(recs["e_noc_xchip"]).sum())
        if "e_noc_xchip" in recs else 0.0,
        "worst_path_latency_s": prog.worst_path_latency_s,
    }


def adaptive_control_workload(**kw) -> dict:
    """Closed-loop adaptive control with on-mesh PES learning (Yan et
    al., arXiv:2009.08921) — the plasticity subsystem's workload.  Lives
    in ``repro.learn.adaptive``; re-exported here (lazily — the learn
    package imports this module's neighbors) so the workload catalog has
    one front door."""
    from repro.learn.adaptive import adaptive_control_workload as f
    return f(**kw)


def stdp_pair_workload(**kw) -> dict:
    """Poisson -> LIF pair with an on-mesh STDP projection (see
    ``repro.learn.adaptive.stdp_pair_workload``)."""
    from repro.learn.adaptive import stdp_pair_workload as f
    return f(**kw)


def hybrid_workload(n_neurons: int = 256, hidden: int = 64,
                    n_ticks: int = 600, mesh: MeshSpec | None = None,
                    seed: int = 0) -> dict:
    """Compile and run the hybrid NEF -> event-MAC pipeline on the mesh."""
    graph = hybrid_graph(n_neurons, hidden, n_ticks=n_ticks, seed=seed)
    sim = ChipSim(compile_graph(graph, mesh))
    recs = sim.run(n_ticks)

    x = graph.populations[0].meta["x"]
    xhat = np.asarray(recs["xhat"])
    spikes_per_tick = np.asarray(recs["n_spk"], np.float64)
    total_spikes = float(spikes_per_tick.sum())
    active = spikes_per_tick > 0
    e_mac = event_mac_energy_j(total_spikes, 1, hidden)
    e_frame = event_mac_energy_j(n_ticks, n_neurons, hidden)
    e_tick = (n_neurons * paper.NEF_E_NEURON_J
              + spikes_per_tick * 1 * 0.2e-9)
    ens = graph.semantics.ens
    return {
        "sim": sim, "recs": recs, "table": chip_power_table(sim, recs),
        "xhat": xhat,
        "x": x,
        "rmse": float(np.sqrt(np.mean(
            (xhat[n_ticks // 4:, 0] - x[n_ticks // 4:, 0]) ** 2))),
        "n_dispatched": int(np.asarray(recs["n_dispatched"]).sum()),
        "total_spikes": total_spikes,
        "duty_cycle": float(active.mean()),
        "energy_mac_j": e_mac,
        "energy_mac_frame_j": e_frame,
        "event_vs_frame": e_mac / e_frame,
        "energy_noc_j": float(np.asarray(recs["e_noc"]).sum()),
        "link_loads": np.asarray(recs["link_flits"]),
        "graded_bits_out": np.asarray(recs["graded_bits_out"]).sum(axis=1),
        "graded_bits_in": np.asarray(recs["graded_bits_in"]).sum(axis=1),
        "synops": synop_metrics(ens, spikes_per_tick, e_tick),
        "hidden_out": np.asarray(recs["hidden_out"]),
    }
