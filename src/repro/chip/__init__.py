"""Chip-level mesh simulator (paper Sec. III-A/B scaled out).

Unified workload API: declare any SNN / DNN / hybrid workload as a
``NetGraph`` (populations + typed spike/graded projections), compile it to
a ``ChipProgram`` (SRAM-constrained placement, routing tables, multicast
link-incidence tensors), and run it on the ONE workload-agnostic engine:

    graph = workloads.synfire_graph(8)          # or dnn_graph / hybrid_graph
    prog  = compile(graph)                      # placement + routing + NoC
    sim   = ChipSim(prog)
    recs  = sim.run(n_ticks=1200)               # one lax.scan, all PEs
    table = chip_power_table(sim, recs)         # Table III at chip scale

Modules:

* ``graph``     — ``NetGraph`` / ``Population`` / ``Projection`` and the
  ``TickSemantics`` contract (per-tick step, packets, Eq. (1) energies).
* ``compile``   — graph -> ``ChipProgram`` lowering with clear capacity /
  SRAM errors.
* ``mesh_noc``  — link enumeration, arithmetic X/Y multicast-tree
  construction into a CSR ``SparseIncidence``, and per-tick accounting
  (sparse segment reduction or dense einsum, bit-identical) for spike
  AND graded multi-flit packets.
* ``mapping``   — the shared snake-order placement primitive plus the
  legacy direct placers (``place_ring``/``place_layers``).
* ``chip``      — ``ChipSim``: runs any program in one ``lax.scan`` with
  per-PE activity-driven DVFS and chip-level power tables.
* ``workloads`` — graph builders: synfire ring of any length, tiled
  feedforward DNN pipeline, hybrid NEF + event-driven-MAC pipeline (and
  its board-scale ``hybrid_farm_graph`` of independent channels), plus
  ``*_board_graph`` variants sized to a multi-chip board.
* ``stimulus``  — where a network's drive comes from: a table baked in
  at build time, or a window streamed through the scan carry (served
  sessions).

One level up, ``repro.board`` compiles a ``NetGraph`` across a whole
grid of chips (``compile_board``) into a program this same ``ChipSim``
engine runs unchanged — see ``src/repro/board/``.
"""
from repro.chip.mesh_noc import MeshNoc, MeshSpec, SparseIncidence
from repro.chip.mapping import Placement, place_ring, place_layers
from repro.chip.graph import NetGraph, Population, Projection
from repro.chip.compile import ChipProgram, compile
from repro.chip.chip import ChipSim, chip_power_table

__all__ = ["MeshNoc", "MeshSpec", "SparseIncidence", "Placement",
           "place_ring", "place_layers", "NetGraph", "Population",
           "Projection", "ChipProgram", "compile", "ChipSim",
           "chip_power_table"]
