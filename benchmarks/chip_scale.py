"""Chip-scale sweep: compiled workload programs vs. mesh size.

SpiNNCer's result at network scale is that peak COMMUNICATION traffic,
not neuron compute, becomes the bottleneck — this sweep reports exactly
that, now for all three workload classes through the unified
graph -> compile -> ChipProgram pipeline:

* synfire rings 8 -> 64+ PEs: per-PE power stays flat (the DVFS point of
  the paper) while peak link load tracks the wave.
* the tiled-DNN pipeline: frames streamed tick-by-tick, graded activation
  bursts priced in DNoC flits, pipeline latency + MAC/NoC energy.
* the hybrid NEF -> event-MAC program: spike-vector payloads over the
  mesh, event-vs-frame energy, graded-payload conservation.

The board-scale sweep (``--sweep 256,1024,4096``) takes the same three
classes to 1000+ PE meshes through the SPARSE NoC path, reporting graph
build, compile and per-tick engine time separately plus a sparse-vs-dense
microbench of the per-tick link/flit accounting — the numbers behind
BENCH_pr3.json.  ``--probe-overhead`` additionally times the engine with
the default telemetry probe set compiled into the scan (the < 10%
overhead budget of BENCH_pr6.json); ``--exec-mode event|both`` times the
activity-compressed event engine next to (or instead of) the dense rows
and ``--activity`` stamps each row with its mean active-source fraction —
the dense-vs-event pairs behind BENCH_pr8.json; ``--json`` writes a
manifest-stamped artifact.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import (dnn_graph, hybrid_farm_graph,
                                  hybrid_workload, synfire_graph,
                                  tiled_dnn_workload)
from repro.configs import paper
from repro.core.pe import PESpec, partition_layer_to_sram
from repro.obs import PhaseTimers, default_probes, record_link_profile


def main(sizes=(8, 16, 32, 64), ticks_per_pe: int = 12) -> None:
    for n_pes in sizes:
        sim = ChipSim(compile_graph(synfire_graph(n_pes)))
        n_ticks = max(300, ticks_per_pe * n_pes)   # >= one full ring period
        # wall time includes the scan trace (run() is cold each call);
        # block_until_ready so async dispatch doesn't fake the number
        t0 = time.perf_counter()
        recs = jax.block_until_ready(sim.run(n_ticks))
        us = (time.perf_counter() - t0) / n_ticks * 1e6
        tab = chip_power_table(sim, recs)
        m = tab["mesh"]
        emit(f"chip_synfire_{n_pes}pe", us,
             f"mesh={m[0]}x{m[1]};links={tab['noc']['n_links']};"
             f"perPE_dvfs_mW={tab['per_pe']['dvfs']['total']:.1f};"
             f"chip_dvfs_mW={tab['chip']['dvfs']['total']:.0f};"
             f"chip_pl3_mW={tab['chip']['pl3']['total']:.0f};"
             f"noc_uW={tab['noc']['power_mw']*1e3:.2f};"
             f"peak_link={tab['noc']['peak_link_load']:.0f};"
             f"peak_util={tab['noc']['peak_utilization']:.4f};"
             f"worst_hops={tab['noc']['worst_tree_hops']}")

    # tiled DNN: the compiled program streams frames tick-by-tick
    t0 = time.perf_counter()
    rep = jax.block_until_ready(tiled_dnn_workload())
    us = (time.perf_counter() - t0) * 1e6
    tab = rep["table"]
    emit("chip_tiled_dnn_program", us,
         f"pes={rep['n_pes_used']};mesh={rep['mesh'][0]}x{rep['mesh'][1]};"
         f"frames={rep['n_frames_out']};"
         f"latency_ms={rep['latency_s']*1e3:.1f};"
         f"compute_ms={rep['compute_s']*1e3:.1f};"
         f"mac_uJ={rep['energy_mac_j']*1e6:.2f};"
         f"noc_uJ={rep['energy_noc_j']*1e6:.3f};"
         f"peak_link_flits={rep['peak_link_flits']:.0f};"
         f"perPE_dvfs_mW={tab['per_pe']['dvfs']['total']:.1f}")

    # hybrid NEF -> event-MAC: graded spike-vector payloads over the mesh
    t0 = time.perf_counter()
    h = jax.block_until_ready(hybrid_workload(n_ticks=600))
    us = (time.perf_counter() - t0) * 1e6
    conserved = int(np.array_equal(h["graded_bits_out"][:-1],
                                   h["graded_bits_in"][1:]))
    emit("chip_hybrid_program", us,
         f"rmse={h['rmse']:.3f};event_vs_frame={h['event_vs_frame']:.4f};"
         f"spikes={h['total_spikes']:.0f};duty={h['duty_cycle']:.3f};"
         f"pj_per_eq_synop={h['synops']['pj_per_eq_synop']:.1f};"
         f"noc_nJ={h['energy_noc_j']*1e9:.2f};"
         f"payload_conserved={conserved}")


# -------------------------------------------------------------------------
# Board-scale sweep (256 -> 1024 -> 4096 PEs) through the sparse NoC path
# -------------------------------------------------------------------------

# per-core neuron counts scaled down from Table II so a 4096-PE ring's
# weight tensors stay in laptop memory — the mesh/NoC work, which is what
# this sweep measures, is unchanged
SCALED_SYNFIRE = dataclasses.replace(
    paper.SYNFIRE, n_exc=16, n_inh=4, neurons_per_core=20,
    synapses_per_core=400, fan_in_exc=8, fan_in_inh=4, l_th1=2, l_th2=7)

# template conv layer that splits into ~13 tiles under the 128 kB SRAM
SCALE_DNN_LAYER = dict(h=64, w=64, cin=32, cout=64, kh=3, kw=3)


def dnn_layers_for_pes(n_pes: int, pe: PESpec = PESpec()) -> list:
    """Repeat the template layer until the tiled stack fills ~n_pes PEs."""
    _, _, tiles = partition_layer_to_sram(
        pe, **{k: SCALE_DNN_LAYER[k] for k in ("h", "w", "cin", "cout",
                                               "kh", "kw")})
    n_layers = max(2, -(-n_pes // tiles))
    return [dict(SCALE_DNN_LAYER, name=f"conv{i}") for i in range(n_layers)]


def build_scaled_graph(cls: str, n_pes: int):
    if cls == "synfire":
        # shot-noise drive (deterministic per (seed, tick)) with the
        # Gaussian sub-threshold jitter off: the wave still propagates
        # (~1.6 spikes/tick ring-wide) but the background is silent, so
        # the sweep exercises the activity sparsity the event engine
        # compresses.  Dense tick cost is activity-independent, so the
        # dense rows stay comparable to earlier BENCH artifacts.
        return synfire_graph(n_pes, sp=SCALED_SYNFIRE, w_exc=0.25,
                             noise_sigma=0.0, noise_model="shot")
    if cls == "dnn":
        return dnn_graph(dnn_layers_for_pes(n_pes))
    if cls == "hybrid":
        return hybrid_farm_graph(n_pairs=n_pes // 2, n_neurons=32, hidden=16)
    raise ValueError(cls)


def sweep(sizes=(256, 1024, 4096), n_ticks: int = 64,
          classes=("synfire", "dnn", "hybrid"),
          compile_budget_s: float | None = None,
          noc_batch: int = 64, profile_links: bool = False,
          probe_overhead: bool = False, exec_mode: str = "dense",
          activity: bool = False) -> dict:
    """Compile + run each workload class at each mesh size.

    Reported separately per (class, size):
      build_s    — graph construction (weights, drive tables; not ours)
      compile_s  — place + route + CSR incidence (the vectorized compiler)
      jit_s      — first runner call (scan trace + XLA compile, cold)
      tick_us    — engine wall time per tick, auto-selected NoC path
      noc_sparse_us / noc_dense_us — per-tick link+flit accounting alone
                   (jit'd, warmed, batched over ``noc_batch`` ticks), the
                   sparse gather+segment-sum vs the dense einsum
      probe_us / probe_overhead — (with ``probe_overhead=True``) per-tick
                   wall time with the default telemetry probe set in the
                   scan carry, and its relative cost vs the bare engine

    ``exec_mode`` selects the engine execution mode for the timed rows:
    ``"dense"`` (the always-on per-PE tick, baseline-comparable),
    ``"event"`` (activity-compressed ticks, rows suffixed ``_event``) or
    ``"both"`` — a dense/event row PAIR per (class, size), the event row
    carrying ``dense_tick_us`` + ``event_vs_dense`` speedup.  With
    ``activity=True`` each row also reports the run's mean
    ``active_frac`` (active sources / sources per tick).

    ``profile_links`` records per-link peak/mean flit profiles for each
    class's largest mesh through the whole-run link probes (parity with
    ``board_scale.py``), feeding the congestion-aware-routing roadmap
    item from single-chip runs too.  Returns ``{"link_profiles": ...,
    "phase_timers": ...}`` for the JSON artifact.
    """
    rng = np.random.default_rng(0)
    link_profiles: dict = {}
    phase_timers: dict = {}
    for cls in classes:
        for n_pes in sizes:
            tm = PhaseTimers()
            with tm.phase("build"):
                graph = build_scaled_graph(cls, n_pes)
            with tm.phase("compile"):
                prog = compile_graph(graph)
            if compile_budget_s is not None and \
                    tm["compile"] > compile_budget_s:
                raise RuntimeError(
                    f"{cls}@{n_pes}: compile took {tm['compile']:.2f}s "
                    f"> budget {compile_budget_s:.2f}s")

            # engine per-tick, auto-selected NoC path, compiled-once scan:
            # the first call pays the scan trace + XLA compile, the
            # steady-state median is the per-tick number
            modes = ("dense", "event") if exec_mode == "both" \
                else (exec_mode,)
            mode_us: dict = {}
            mode_frac: dict = {}
            sim = None
            for mode in modes:
                msim = ChipSim(prog, exec_mode=mode)
                sim = sim or msim
                runner = jax.jit(lambda s=msim: s.run(n_ticks))
                tag = "first_tick_jit" if mode == modes[0] \
                    else f"first_tick_jit_{mode}"
                with tm.phase(tag):
                    jax.block_until_ready(runner())
                mode_us[mode] = time_call(runner, warmup=0,
                                          iters=3) / n_ticks
                if activity:
                    frac = runner().get("active_frac")
                    if frac is not None:
                        mode_frac[mode] = float(np.asarray(frac).mean())
            tick_us = mode_us[modes[0]]
            tm.record("steady_tick", tick_us * 1e-6)

            probe_str = ""
            if probe_overhead:
                probes = default_probes(prog)
                prunner = jax.jit(lambda: sim.run(n_ticks, probes=probes))
                probe_us = time_call(prunner, warmup=1, iters=3) / n_ticks
                probe_str = (f";probe_us={probe_us:.1f};"
                             f"probe_overhead={probe_us / tick_us - 1:.4f}")

            if profile_links and n_pes == max(sizes):
                # whole-run per-link peak/mean through the probe layer —
                # O(n_links) memory regardless of n_ticks
                link_profiles[f"scale_{cls}_{prog.n_pes}pe"] = \
                    record_link_profile(sim, n_ticks)

            # NoC accounting alone, per tick inside a scan (how the engine
            # pays it): sparse column plan vs dense einsum
            noc = prog.noc
            P = prog.n_pes
            pk0 = jnp.asarray(rng.integers(0, 4, P).astype(np.float32))
            pb = jnp.asarray(prog.payload_bits)
            cols, inv = prog.sinc.device_col_plan()
            inc = jnp.asarray(prog.inc)

            def loads_scan(fn):
                def step(carry, t):
                    p = pk0 * (t % 3).astype(jnp.float32)
                    ll, fl = fn(p)
                    return carry + ll.sum() + fl.sum(), None
                return jax.lax.scan(step, jnp.float32(0),
                                    jnp.arange(noc_batch))[0]

            f_sp = jax.jit(lambda: loads_scan(
                lambda p: noc.noc_loads_sparse(p, cols, inv, pb)))
            f_de = jax.jit(lambda: loads_scan(
                lambda p: (noc.link_loads(p, inc),
                           noc.flit_loads(p, inc, pb))))
            # min over rounds: wall-clock noise is one-sided, the minimum
            # is the best estimator of the true per-tick cost
            sp_us = min(time_call(f_sp, iters=5) for _ in range(3)) \
                / noc_batch
            de_us = min(time_call(f_de, iters=5) for _ in range(3)) \
                / noc_batch

            base = f"scale_{cls}_{P}pe"
            phase_timers[base] = tm.asdict()
            shared = (
                f"mesh={prog.mesh.width}x{prog.mesh.height};"
                f"links={noc.n_links};nnz={prog.sinc.nnz};"
                f"density={prog.sinc.density:.4f};"
                f"build_s={tm['build']:.3f};compile_s={tm['compile']:.3f};"
                f"jit_s={tm['first_tick_jit']:.3f};"
                f"noc_sparse_us={sp_us:.2f};noc_dense_us={de_us:.2f};"
                f"noc_speedup={de_us / sp_us:.2f};"
                f"worst_hops={prog.worst_tree_hops}{probe_str}")
            for mode in modes:
                name = base if mode == "dense" else f"{base}_{mode}"
                extra = f";exec_mode={mode}"
                if mode in mode_frac:
                    extra += f";active_frac={mode_frac[mode]:.4f}"
                if mode == "event" and "dense" in mode_us:
                    extra += (f";dense_tick_us={mode_us['dense']:.1f};"
                              f"event_vs_dense="
                              f"{mode_us['dense'] / mode_us[mode]:.2f}")
                emit(name, mode_us[mode], shared + extra)
    return {"link_profiles": link_profiles, "phase_timers": phase_timers}


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep", default=None, metavar="SIZES",
                    help="comma list of PE counts, e.g. 256,1024,4096 — "
                    "run the board-scale sweep instead of the CI smoke")
    ap.add_argument("--classes", default="synfire,dnn,hybrid")
    ap.add_argument("--ticks", type=int, default=64)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if any compile exceeds this many seconds")
    ap.add_argument("--profile-links", action="store_true",
                    help="record per-link peak/mean load profiles for "
                    "each class's largest mesh (parity with board_scale)")
    ap.add_argument("--probe-overhead", action="store_true",
                    help="also time the engine with the default telemetry "
                    "probe set (the BENCH_pr6 < 10%% overhead budget)")
    ap.add_argument("--exec-mode", default="dense",
                    choices=["dense", "event", "both"],
                    help="engine execution mode for the sweep rows; "
                    "'both' emits a dense/event row pair per (class, "
                    "size) with the event-vs-dense speedup")
    ap.add_argument("--activity", action="store_true",
                    help="record a run per mode and add its mean "
                    "active-source fraction to each sweep row")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows as machine-readable JSON "
                    "(manifest-stamped)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    extras: dict = {}
    if args.sweep:
        extras = sweep(sizes=tuple(int(s) for s in args.sweep.split(",")),
                       n_ticks=args.ticks,
                       classes=tuple(args.classes.split(",")),
                       compile_budget_s=args.budget_s,
                       profile_links=args.profile_links,
                       probe_overhead=args.probe_overhead,
                       exec_mode=args.exec_mode,
                       activity=args.activity)
    else:
        main()

    if args.json:
        from benchmarks.common import RESULTS
        from repro.obs import write_bench_json
        write_bench_json(args.json, RESULTS,
                         link_profiles=extras.get("link_profiles", {}),
                         timers=extras.get("phase_timers"),
                         config=vars(args))
