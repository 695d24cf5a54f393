"""Run workload graphs across a full PE mesh and watch the NoC.

    PYTHONPATH=src python examples/chip_mesh.py [--pes 64] [--ticks 700]
        [--workload synfire|dnn|hybrid]

The unified API: build a ``NetGraph``, ``compile`` it to a ``ChipProgram``
(placement + routing + incidence), run it on the workload-agnostic
``ChipSim``.  Prints the mesh layout, a raster/occupancy view, the busiest
links, and the chip-level power table (per-PE Table III numbers scaled to
the mesh plus NoC power/congestion).
"""
import argparse

import numpy as np

from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import (hybrid_workload, synfire_graph,
                                  tiled_dnn_workload)


def print_noc_and_power(sim, recs):
    loads = np.asarray(recs["link_load"])                 # (T, L)
    flits = np.asarray(recs["link_flits"])
    busiest = np.argsort(flits.sum(axis=0))[::-1][:5]
    print("\nbusiest links (total over the run):")
    for li in busiest:
        (a, b) = sim.noc.links[li]
        print(f"  {a} -> {b}: {loads[:, li].sum():.0f} packets / "
              f"{flits[:, li].sum():.0f} flits, "
              f"peak {flits[:, li].max():.0f} flits/tick")

    tab = chip_power_table(sim, recs)
    print(f"\nper-PE: DVFS {tab['per_pe']['dvfs']['total']:.1f} mW, "
          f"only-PL3 {tab['per_pe']['pl3']['total']:.1f} mW "
          f"(reduction {tab['per_pe']['reduction']['total']*100:.1f}%)")
    print(f"chip ({tab['n_pes']} PEs): DVFS "
          f"{tab['chip']['dvfs']['total']/1e3:.2f} W, only-PL3 "
          f"{tab['chip']['pl3']['total']/1e3:.2f} W")
    print(f"NoC: {tab['noc']['power_mw']*1e3:.2f} uW, peak link load "
          f"{tab['noc']['peak_link_flits']:.0f} flits/tick "
          f"({tab['noc']['peak_utilization']*100:.2f}% of capacity), "
          f"worst multicast depth {tab['noc']['worst_tree_hops']} hops")


def run_synfire(args):
    graph = synfire_graph(args.pes)
    prog = compile_graph(graph)
    sim = ChipSim(prog, exec_mode=args.exec_mode)
    m = prog.mesh
    print(f"{args.pes}-PE synfire ring on a {m.width}x{m.height} QPE mesh "
          f"({prog.noc.n_links} directed links), "
          f"exec_mode={args.exec_mode}")

    recs = sim.run(args.ticks)
    spk = np.asarray(recs["spikes_exc"]).sum(axis=2)      # (T, P)
    show = list(range(0, args.pes, max(1, args.pes // 8)))
    bins = spk[: args.ticks - args.ticks % 8].reshape(-1, 8, args.pes)
    bins = bins.sum(axis=1)
    print("\nspike raster (rows = sampled PEs, cols = 8 ms bins)")
    for p in show:
        row = "".join("#" if b > 100 else ("." if b > 0 else " ")
                      for b in bins[:90, p])
        print(f"PE{p:3d} |{row}|")
    print_noc_and_power(sim, recs)


def run_dnn(args):
    rep = tiled_dnn_workload()
    prog = rep["sim"].program
    print(f"tiled DNN: {rep['n_pes_used']} tile-PEs on a "
          f"{rep['mesh'][0]}x{rep['mesh'][1]} QPE mesh; "
          f"{rep['n_frames_out']} frames through the pipeline, "
          f"first-frame latency {rep['latency_s']*1e3:.1f} ms")
    busy = np.asarray(rep["recs"]["busy"])                # (T, P)
    print("\npipeline occupancy (rows = tile PEs, cols = ticks)")
    for p in range(prog.n_pes):
        row = "".join("#" if b else "." for b in busy[:70, p])
        print(f"PE{p:3d} |{row}|")
    print_noc_and_power(rep["sim"], rep["recs"])


def run_hybrid(args):
    h = hybrid_workload(n_ticks=max(args.ticks, 400))
    print(f"hybrid NEF->event-MAC: rmse {h['rmse']:.3f}, duty cycle "
          f"{h['duty_cycle']*100:.0f}%, event/frame MAC energy "
          f"{h['event_vs_frame']:.3f}")
    print(f"graded payload conservation: "
          f"{h['graded_bits_out'][:-1].sum():.0f} bits out == "
          f"{h['graded_bits_in'][1:].sum():.0f} bits in")
    print_noc_and_power(h["sim"], h["recs"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pes", type=int, default=64)
    ap.add_argument("--ticks", type=int, default=700)
    ap.add_argument("--workload", default="synfire",
                    choices=["synfire", "dnn", "hybrid"])
    ap.add_argument("--exec-mode", default="auto",
                    choices=["auto", "dense", "event"],
                    help="engine execution mode (synfire workload): the "
                    "event engine is bitwise-identical to dense")
    args = ap.parse_args()
    {"synfire": run_synfire, "dnn": run_dnn, "hybrid": run_hybrid}[
        args.workload](args)


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
