"""Board-scale benchmark: one ``NetGraph`` compiled across multi-chip
SpiNNaker 2 boards (the numbers behind BENCH_pr4.json).

For each (workload class, board) pair this reports, separately:

  build_s      — graph construction (weights, drive tables; not ours)
  partition_s  — min-cut-flavored population -> chip assignment
  compile_s    — per-chip snake placement + hierarchical routing into
                 the board-wide CSR incidence (sub-quadratic in total
                 PEs: O(sum of stitched tree sizes))
  jit_s        — first runner call (scan trace + XLA compile, cold)
  tick_us      — engine wall time per tick through the auto-selected
                 sparse NoC path (one lax.scan for the whole board)
  xchip_*      — the traffic split: share of flits / NoC energy riding
                 the expensive chip-to-chip tier, peak chip-to-chip
                 link flits vs. capacity

The headline configuration is the 48-chip board (``--boards 4x12
--chip 4x2`` = 1536 PEs) running the hybrid NEF->event-MAC farm; the
default sweep walks 1x1 -> 2x2 -> 4x6 -> 4x12 so compile-time scaling
is visible in one artifact.  ``--profile-links`` additionally records
per-link peak/mean loads through the whole-run link probes
(``repro.obs``) — the real traffic profiles the congestion-aware-routing
roadmap item needs.  ``--json`` writes a manifest-stamped artifact.
"""
from __future__ import annotations

import jax

from benchmarks.common import emit, time_call
from repro.board import BoardSpec, compile_board, partition
from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.workloads import (dnn_board_graph, hybrid_farm_board_graph,
                                  synfire_board_graph)
from repro.obs import PhaseTimers, record_link_profile
from repro.routeopt import optimize_routes

# per-core neuron counts scaled down from Table II so a 1536-PE ring's
# weight tensors stay in laptop memory (same scaling as chip_scale.py)
from benchmarks.chip_scale import SCALED_SYNFIRE

BUILDERS = {
    "synfire": lambda b: synfire_board_graph(b, sp=SCALED_SYNFIRE),
    "dnn": dnn_board_graph,
    "hybrid": hybrid_farm_board_graph,
}


def bench_board(cls: str, board: BoardSpec, n_ticks: int = 64,
                compile_budget_s: float | None = None,
                profile_links: bool = False) -> dict:
    """One (class, board) row.  Returns ``{"name", "timers",
    "link_profile"}`` so the caller can assemble the JSON artifact
    without module-level globals."""
    tm = PhaseTimers()
    with tm.phase("build"):
        graph = BUILDERS[cls](board)
    with tm.phase("partition"):
        part = partition(graph, board)
    with tm.phase("compile"):
        prog = compile_board(graph, board, part=part)
    if compile_budget_s is not None and \
            tm["partition"] + tm["compile"] > compile_budget_s:
        raise RuntimeError(
            f"{cls}@{board.chips_x}x{board.chips_y}: partition+compile "
            f"took {tm['partition'] + tm['compile']:.2f}s > budget "
            f"{compile_budget_s:.2f}s")

    sim = ChipSim(prog)
    runner = jax.jit(lambda: sim.run(n_ticks))
    with tm.phase("first_tick_jit"):
        jax.block_until_ready(runner())
    tick_us = time_call(runner, warmup=0, iters=3) / n_ticks
    tm.record("steady_tick", tick_us * 1e-6)
    recs = jax.block_until_ready(sim.run(n_ticks))
    tab = chip_power_table(sim, recs)

    name = (f"board_{cls}_{board.chips_x}x{board.chips_y}chips_"
            f"{prog.n_pes}pe")
    x = tab["noc"].get("xchip", {})
    emit(name, tick_us,
         f"chips={board.n_chips};chip={board.chip.width}x"
         f"{board.chip.height};pes={prog.n_pes};links={prog.noc.n_links};"
         f"xlinks={prog.noc.n_xchip_links};nnz={prog.sinc.nnz};"
         f"density={prog.sinc.density:.5f};cut_flits={part.cut_flits:.0f};"
         f"build_s={tm['build']:.3f};partition_s={tm['partition']:.3f};"
         f"compile_s={tm['compile']:.3f};jit_s={tm['first_tick_jit']:.3f};"
         f"xchip_flit_frac={x.get('flits_frac', 0.0):.4f};"
         f"xchip_energy_frac={x.get('energy_frac', 0.0):.4f};"
         f"peak_xlink_flits={x.get('peak_xlink_flits', 0.0):.0f};"
         f"peak_link_flits={tab['noc']['peak_link_flits']:.0f};"
         f"noc_power_mw={tab['noc']['power_mw']:.4f};"
         f"worst_hops={prog.worst_tree_hops}")

    out = {"name": name, "timers": tm.asdict(), "link_profile": None}
    if profile_links:
        # the congestion-aware-routing seed: real per-link profiles off
        # the whole-run link probes, split at the tier boundary (ids >=
        # n_onchip_links are chip-to-chip)
        out["link_profile"] = record_link_profile(sim, n_ticks)
    return out


def bench_board_opt(cls: str, board: BoardSpec, n_ticks: int = 64,
                    opt_iters: int = 4,
                    compile_budget_s: float | None = None) -> dict:
    """The optimized twin of a ``bench_board`` row: run the
    profile-guided route/place loop (``repro.routeopt``) on the same
    (class, board) pair and emit a ``..._opt`` row carrying both sides
    — optimized peak/mean per tier next to the measured baseline — plus
    the per-iteration trajectory for the JSON artifact.  The
    optimizer's wall-clock budget is the same ``--budget-s`` the plain
    compile is held to (equal compile budget, the PR 9 gate)."""
    tm = PhaseTimers()
    with tm.phase("build"):
        graph = BUILDERS[cls](board)
    with tm.phase("optimize"):
        res = optimize_routes(graph, board, n_ticks=n_ticks,
                              max_iters=opt_iters,
                              budget_s=compile_budget_s)
    prog = res.program
    sim = ChipSim(prog)
    runner = jax.jit(lambda: sim.run(n_ticks))
    with tm.phase("first_tick_jit"):
        jax.block_until_ready(runner())
    tick_us = time_call(runner, warmup=0, iters=3) / n_ticks
    tm.record("steady_tick", tick_us * 1e-6)

    base, opt = res.baseline, res.profile
    name = (f"board_{cls}_{board.chips_x}x{board.chips_y}chips_"
            f"{prog.n_pes}pe_opt")
    emit(name, tick_us,
         f"chips={board.n_chips};pes={prog.n_pes};"
         f"ports={prog.board.ports_per_edge};"
         f"iters={res.iterations};converged={int(res.converged)};"
         f"optimize_s={tm['optimize']:.3f};"
         f"peak_xlink_flits={opt.peak_xlink:.0f};"
         f"base_peak_xlink_flits={base.peak_xlink:.0f};"
         f"mean_xlink_flits={opt.mean_xlink:.4f};"
         f"base_mean_xlink_flits={base.mean_xlink:.4f};"
         f"peak_onchip_flits={opt.peak_onchip:.0f};"
         f"base_peak_onchip_flits={base.peak_onchip:.0f};"
         f"improvement={res.improvement:.4f}")
    return {"name": name, "timers": tm.asdict(),
            "trajectory": res.trajectory}


def main(boards=("1x1", "2x2", "4x6", "4x12"), chip: str = "4x2",
         classes=("hybrid", "synfire", "dnn"), n_ticks: int = 64,
         compile_budget_s: float | None = None,
         profile_links: bool = False, route_opt: bool = False,
         opt_iters: int = 4) -> dict:
    link_profiles: dict = {}
    phase_timers: dict = {}
    route_opt_traj: dict = {}
    for cls in classes:
        for i, b in enumerate(boards):
            spec = BoardSpec.parse(b, chip=chip)
            row = bench_board(cls, spec, n_ticks=n_ticks,
                              compile_budget_s=compile_budget_s,
                              # profiles only for each class's largest board
                              profile_links=profile_links
                              and i == len(boards) - 1)
            phase_timers[row["name"]] = row["timers"]
            if row["link_profile"] is not None:
                link_profiles[row["name"]] = row["link_profile"]
            if route_opt and spec.n_chips > 1:
                orow = bench_board_opt(cls, spec, n_ticks=n_ticks,
                                       opt_iters=opt_iters,
                                       compile_budget_s=compile_budget_s)
                phase_timers[orow["name"]] = orow["timers"]
                route_opt_traj[orow["name"]] = orow["trajectory"]
    return {"link_profiles": link_profiles, "phase_timers": phase_timers,
            "route_opt": route_opt_traj}


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--boards", default="1x1,2x2,4x6,4x12",
                    help="comma list of chip grids, e.g. 2x2,4x12")
    ap.add_argument("--chip", default="4x2",
                    help="per-chip QPE mesh, e.g. 4x2 (= 32 PEs)")
    ap.add_argument("--classes", default="hybrid,synfire,dnn")
    ap.add_argument("--ticks", type=int, default=64)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if any partition+compile exceeds this")
    ap.add_argument("--profile-links", action="store_true",
                    help="record per-link peak/mean load profiles")
    ap.add_argument("--route-opt", action="store_true",
                    help="pair each multi-chip row with a profile-guided "
                         "route/place-optimized twin (repro.routeopt)")
    ap.add_argument("--opt-iters", type=int, default=4,
                    help="max optimizer iterations per --route-opt row")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    extras = main(boards=tuple(args.boards.split(",")), chip=args.chip,
                  classes=tuple(args.classes.split(",")),
                  n_ticks=args.ticks, compile_budget_s=args.budget_s,
                  profile_links=args.profile_links,
                  route_opt=args.route_opt, opt_iters=args.opt_iters)

    if args.json:
        from benchmarks.common import RESULTS
        from repro.obs import write_bench_json
        write_bench_json(args.json, RESULTS,
                         link_profiles=extras["link_profiles"],
                         timers=extras["phase_timers"],
                         config=vars(args),
                         route_opt=extras["route_opt"])
