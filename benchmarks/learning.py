"""On-mesh learning benchmark: the numbers behind BENCH_pr5.json.

Three questions, answered per row:

* **does it learn?** — the adaptive-control loop (NEF ensemble + PES
  decoders tracking a reference plant, after Yan et al. 2009.08921)
  reports its convergence tick (first tick after which the worst
  channel's windowed tracking error stays below the threshold) and the
  final error, on a single chip AND across a 2x2 board through the
  UNCHANGED ``compile_board`` path (``refine=False`` keeps the loops
  split across chips, so weight updates are driven by errors that rode
  the SerDes tier);
* **what does it cost per tick?** — engine wall time per tick of the
  plastic program vs its frozen twin (same graph, ``plasticity=None``,
  fixed decoders) — the tick_us overhead of carrying + updating
  weights in the scan;
* **what does it cost in energy?** — the ``e_learn`` share of total
  chip energy (MAC-class weight updates + exp-accelerator trace decays
  vs Eq. (1) datapath + NoC traffic).

The STDP pair row exercises the fixed-point trace path (s16.15 decay
through the exp accelerator kernel) with the same three readouts.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import RESULTS, emit, time_call
from repro.board import BoardSpec
from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.learn.adaptive import (adaptive_control_graph,
                                  adaptive_control_workload,
                                  stdp_pair_workload)


def _tick_us(prog, n_ticks: int) -> float:
    sim = ChipSim(prog)
    runner = jax.jit(lambda: sim.run(n_ticks))
    return time_call(runner, warmup=1, iters=3) / n_ticks


def bench_adaptive(n_channels: int, n_neurons: int, n_ticks: int,
                   board: BoardSpec | None = None,
                   err_threshold: float = 0.1) -> None:
    where = (f"board{board.chips_x}x{board.chips_y}" if board is not None
             else "chip")
    name = f"learn_adaptive_{where}_{n_channels}ch"
    t0 = time.perf_counter()
    rep = adaptive_control_workload(
        n_channels=n_channels, n_neurons=n_neurons, n_ticks=n_ticks,
        board=board, err_threshold=err_threshold, refine=False)
    wall_s = time.perf_counter() - t0

    # tick cost: plastic vs frozen twin (same graph, plasticity=None)
    tick_us = _tick_us(rep["program"], n_ticks=64)
    frozen = adaptive_control_graph(n_channels, n_neurons, n_ticks=n_ticks,
                                    plastic=False)
    if board is not None:
        from repro.board import compile_board
        fprog = compile_board(frozen, board, refine=False)
    else:
        fprog = compile_graph(frozen)
    frozen_us = _tick_us(fprog, n_ticks=64)

    recs = rep["recs"]
    xf = (float(np.asarray(recs["flits_xchip"]).sum())
          if "flits_xchip" in recs else 0.0)
    emit(name, tick_us,
         f"channels={n_channels};neurons={n_neurons};"
         f"pes={rep['program'].n_pes};ticks={n_ticks};"
         f"conv_tick={rep['convergence_tick']};"
         f"final_err={rep['final_err']:.4f};"
         f"initial_err={rep['initial_err']:.4f};"
         f"err_threshold={err_threshold};"
         f"frozen_tick_us={frozen_us:.1f};"
         f"learn_overhead={tick_us / frozen_us - 1.0:.3f};"
         f"e_learn_mj={rep['e_learn_j'] * 1e3:.4f};"
         f"learn_energy_frac={rep['learn_energy_frac']:.4f};"
         f"xchip_flits={xf:.0f};wall_s={wall_s:.2f}")
    if rep["convergence_tick"] < 0:
        raise RuntimeError(
            f"{name}: tracking error never settled below {err_threshold} "
            f"(final {rep['final_err']:.3f}) — the closed loop must "
            f"converge for the row to be meaningful")


def bench_stdp(n_pre: int = 24, n_post: int = 8, n_ticks: int = 512) -> None:
    t0 = time.perf_counter()
    rep = stdp_pair_workload(n_pre=n_pre, n_post=n_post, n_ticks=n_ticks)
    wall_s = time.perf_counter() - t0
    tick_us = _tick_us(rep["program"], n_ticks=64)
    emit("learn_stdp_pair", tick_us,
         f"n_pre={n_pre};n_post={n_post};ticks={n_ticks};"
         f"w_mean_first={rep['w_mean_first']:.4f};"
         f"w_mean_last={rep['w_mean_last']:.4f};"
         f"post_spikes={rep['post_spikes']:.0f};"
         f"e_learn_mj={rep['e_learn_j'] * 1e3:.5f};"
         f"learn_energy_frac={rep['learn_energy_frac']:.5f};"
         f"wall_s={wall_s:.2f}")


def main(n_channels: int = 6, n_neurons: int = 100, n_ticks: int = 2048,
         board: str = "2x2", chip: str = "2x1",
         budget_s: float | None = None) -> None:
    t0 = time.perf_counter()
    bench_adaptive(n_channels, n_neurons, n_ticks)
    bench_adaptive(n_channels, n_neurons, n_ticks,
                   board=BoardSpec.parse(board, chip=chip))
    bench_stdp(n_ticks=min(n_ticks, 512))
    wall = time.perf_counter() - t0
    if budget_s is not None and wall > budget_s:
        raise RuntimeError(f"learning benchmark took {wall:.1f}s "
                           f"> budget {budget_s:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--channels", type=int, default=6)
    ap.add_argument("--neurons", type=int, default=100)
    ap.add_argument("--ticks", type=int, default=2048)
    ap.add_argument("--board", default="2x2")
    ap.add_argument("--chip", default="2x1")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if the whole run exceeds this many seconds")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    main(n_channels=args.channels, n_neurons=args.neurons,
         n_ticks=args.ticks, board=args.board, chip=args.chip,
         budget_s=args.budget_s)

    if args.json:
        from repro.obs import write_bench_json
        write_bench_json(args.json, RESULTS, config=vars(args))
