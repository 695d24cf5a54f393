"""Engine cells: back-to-back jobs of ``ChipSim(program).run(ticks)``.

Set-up builds the program from the seed, makes a ``ChipSim`` with its
default settings (``auto`` everywhere, as a user runs it) and runs one
job, which compiles the scan.  The window then runs jobs back to back,
each ended by ``block_until_ready`` on all of its records; a job's
records are dropped before the next job is dispatched, so at most one
job's records live on the device.  The last job's records are read back
after the window and compared, at full size, with the configuration's
plain reference.

Traffic file keys: ``ticks_per_job``, and ``drive``: keyword arguments
of the background input, passed to the builder and to the reference.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import device, trace
from bench.compiles import CompileCounter
from bench.spec import Cell, load_reference, resolve


def build_program(config: dict, traffic: dict, build_seed: int):
    graph = resolve(config["graph"])(**config["args"],
                                     **{config["seed_arg"]: build_seed},
                                     **traffic.get("drive", {}))
    return resolve(config["compile"])(graph)


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, devs: list) -> dict:
    import jax
    from repro.chip.chip import ChipSim
    cfg, trf = cell.config, cell.traffic
    build_seed, noise_seed = device.sub_seeds(seed, 2)
    n = int(trf["ticks_per_job"])

    program = build_program(cfg, trf, build_seed)
    sim = ChipSim(program)
    jax.block_until_ready(sim.run(n, seed=noise_seed))
    setup_s = time.perf_counter() - t_start

    compiles = CompileCounter()
    with trace.capture(traced) as tr:
        trace.mark(trace.WINDOW_START)
        with compiles:
            t0 = time.perf_counter()
            ends, recs = [], None
            while not ends or ends[-1] - t0 < seconds:
                recs = None
                with trace.span("bench.job"):
                    recs = jax.block_until_ready(sim.run(n, seed=noise_seed))
                ends.append(time.perf_counter())
            jobs, window_s = len(ends), ends[-1] - t0
        trace.mark(trace.WINDOW_END)
    job_s = np.diff([t0] + ends)
    print(f"bench: {jobs} jobs, seconds each min {job_s.min():.6f} median "
          f"{np.median(job_s):.6f} max {job_s.max():.6f}; "
          f"{compiles.count} compiles in the window", file=sys.stderr)
    dev = device.describe(devs)
    got = {k: np.asarray(v) for k, v in recs.items()}
    del recs, sim, program
    gc.collect()

    ref_mod = load_reference(cfg)
    ref, net = ref_mod.records(cfg, trf, build_seed, noise_seed, n)
    checks = ref_mod.compare(ref, got)
    window = {"window_s": window_s, "ticks": jobs * n, "jobs": jobs,
              "compiles_in_window": compiles.count,
              "peaks": device.peaks_for(dev["kind"])}
    if traced:
        window["trace"] = trace.reduce(trace.load(tr.path), chips=cell.chips)
        tr.close()
        window["work"] = ref_mod.tick_work(got, net)
    return {
        "e2e": {"tick_us": window_s / (jobs * n) * 1e6, "setup_s": setup_s},
        "attempted": jobs, "failed": 0, "checks": checks,
        "limits": cfg["limits"], "window": window, "device": dev,
    }


def control(cell: Cell, seed: int, seconds: float) -> dict:
    """The comparison's numbers with the reference in bfloat16 put in
    the program's place: one job of this seed's network and noise."""
    build_seed, noise_seed = device.sub_seeds(seed, 2)
    ref_mod = load_reference(cell.config)
    n = int(cell.traffic["ticks_per_job"])
    ref, net = ref_mod.records(cell.config, cell.traffic, build_seed,
                               noise_seed, n)
    low, _ = ref_mod.records(cell.config, cell.traffic, build_seed,
                             noise_seed, n, precision="bf16", net=net)
    return ref_mod.compare(ref, low)
