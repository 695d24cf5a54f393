"""Declarative workload graphs for the chip-level simulator.

The paper's claim is ONE PE architecture for three workload classes — SNN,
DNN and hybrid SNN/DNN.  This module is the matching programming model: a
workload is a ``NetGraph`` of ``Population`` nodes (neuron populations, DNN
layer tiles, NEF ensembles — anything with an SRAM footprint and per-tick
step semantics) joined by typed ``Projection`` edges that carry either
binary spike events (header-only DNoC packets) or graded payloads
(multi-flit packets, e.g. activations or NEF spike vectors).

``repro.chip.compile.compile(graph, mesh)`` lowers a graph to a
``ChipProgram`` (placement + routing + incidence tensors); the
workload-agnostic engine ``repro.chip.chip.ChipSim`` then runs any program
in one ``jax.lax.scan``.  The per-tick behaviour of a graph is supplied by
its ``TickSemantics`` — the contract is small:

    init_state(program)              -> state pytree
    make_tick(program, dvfs, em, key)-> tick(state, t) -> (state, rec)

where ``rec`` must contain, per logical PE,

    packets  (P,)  multicast packets emitted this tick (NoC sources)
    pl       (P,)  selected performance level (DVFS)
    e_dvfs_baseline/neuron/synapse, e_pl3_baseline/neuron/synapse (P,)
                   the Eq. (1) energy split under DVFS and only-PL3

and may contain ``payload_bits`` (P,) to override the program's static
per-packet payload size for graded traffic that varies tick to tick.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Protocol, runtime_checkable

import jax.numpy as jnp

from repro.configs import paper

SPIKE = "spike"      # binary events: header-only 64 b DNoC packet
GRADED = "graded"    # graded payload: header + ceil(bits/128) 192 b flits


@dataclass(frozen=True)
class Population:
    """One logical node of a workload graph.

    ``n`` is the unit count (neurons, activations, ...); ``n_tiles`` is how
    many PEs the node occupies after SRAM partitioning (the compiler places
    tiles on consecutive PEs); ``sram_bytes`` is the per-tile footprint the
    compiler validates against the 128 kB PE SRAM.  ``align_qpe`` forces the
    node onto a fresh QPE so inter-node traffic crosses real mesh links
    (used by the hybrid workload to keep the SNN and DNN paths on separate
    quads, as on the test chip).
    """
    name: str
    n: int
    sram_bytes: int
    n_tiles: int = 1
    align_qpe: bool = False
    meta: dict = field(default_factory=dict)


@dataclass(frozen=True)
class Projection:
    """Typed edge: every PE of ``src`` multicasts to every PE of ``dst``.

    ``payload`` selects the DNoC packet class: SPIKE packets are header-only
    (64 b effective); GRADED packets carry ``bits_per_packet`` payload bits,
    priced as ceil(bits / 128) flits of 192 bits per link traversal
    (paper Sec. III-A).  ``delay_ticks`` is the synaptic/transport delay the
    semantics honours between emission and arrival.

    ``plasticity`` makes the projection trainable on-mesh: attach a
    ``repro.learn.STDP`` (SPIKE projections) or ``repro.learn.PES``
    (GRADED projections) descriptor and the compiler lowers it into a
    ``LearnSlot`` on the program; the engine then updates the
    projection's weights tick by tick inside the scan and reports the
    per-PE learning energy as ``e_learn`` (see ``repro.learn``).  The
    default ``None`` keeps the projection frozen — and the compiled
    program bitwise identical to the pre-plasticity engine.
    """
    src: str
    dst: str
    payload: str = SPIKE
    bits_per_packet: int = 0
    delay_ticks: int = 1
    plasticity: object = None

    def __post_init__(self):
        if self.payload not in (SPIKE, GRADED):
            raise ValueError(f"unknown payload class {self.payload!r}")
        if self.payload == GRADED and self.bits_per_packet <= 0:
            raise ValueError(
                f"graded projection {self.src}->{self.dst} needs "
                f"bits_per_packet > 0")
        if self.payload == SPIKE and self.bits_per_packet:
            raise ValueError(
                f"spike projection {self.src}->{self.dst} must not carry "
                f"payload bits (got {self.bits_per_packet})")


@runtime_checkable
class TickSemantics(Protocol):
    """Per-tick behaviour of a compiled graph (see module docstring)."""

    def init_state(self, program): ...

    def make_tick(self, program, *, dvfs, em, key): ...


@dataclass
class NetGraph:
    """Ordered populations + typed projections + tick semantics.

    ``board`` is the ``repro.board.BoardSpec`` a board-sized builder
    (``repro.chip.workloads.hybrid_farm_board_graph``) sized the graph
    for, which ``repro.board.compile_for_board`` compiles it onto; None
    for a graph sized for no board."""
    populations: list
    projections: list
    semantics: Optional[TickSemantics] = None
    name: str = "net"
    board: object = None

    def __post_init__(self):
        known, dup = set(), set()
        for p in self.populations:
            (dup if p.name in known else known).add(p.name)
        if dup:
            raise ValueError(f"duplicate population names: {sorted(dup)}")
        for pr in self.projections:
            for end in (pr.src, pr.dst):
                if end not in known:
                    raise ValueError(
                        f"projection {pr.src}->{pr.dst} references unknown "
                        f"population {end!r}; have {sorted(known)}")

    # -- derived ----------------------------------------------------------

    @property
    def n_tiles_total(self) -> int:
        return sum(p.n_tiles for p in self.populations)

    def population(self, name: str) -> Population:
        for p in self.populations:
            if p.name == name:
                return p
        raise KeyError(name)

    def out_projections(self, name: str) -> list:
        return [pr for pr in self.projections if pr.src == name]

    def in_projections(self, name: str) -> list:
        return [pr for pr in self.projections if pr.dst == name]


# ---------------------------------------------------------------------------
# Shared accounting helpers for semantics implementations
# ---------------------------------------------------------------------------

def busy_window_energy(pl, busy_cycles, *, pls=paper.PERF_LEVELS,
                       t_sys_s: float = 1e-3, dvfs: bool = True):
    """Eq. (1) baseline term for a datapath busy ``busy_cycles`` this tick.

    The generalization of ``PEEnergyModel.tick_energy``'s baseline to
    non-SNN workloads: busy time is the cycle count at the selected PL's
    clock, the idle remainder runs at PL1 (dvfs=True) or stays at the
    selected PL (dvfs=False, the "only PL3" comparison mode).
    """
    freqs = jnp.asarray([p.freq_hz for p in pls])
    p_bl = jnp.asarray([p.p_baseline_w for p in pls])
    t_sp = jnp.minimum(busy_cycles / freqs[pl], t_sys_s)
    if dvfs:
        return p_bl[pl] * t_sp + p_bl[0] * (t_sys_s - t_sp)
    return p_bl[pl] * t_sys_s


def mac_dynamic_energy_j(macs, *, tops_per_w: float | None = None):
    """Dynamic energy of ``macs`` MAC-array ops (2 ops each) this tick."""
    tops_per_w = tops_per_w or paper.MAC_TOPS_PER_W[(paper.MEP_VDD,
                                                     paper.MEP_FREQ)]
    return 2.0 * macs / (tops_per_w * 1e12)
