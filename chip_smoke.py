"""Smoke test of the main path on one TPU chip.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process),
through the library's own entry points, and checks its own results:

1. **Synfire ring** — ``synfire_graph(4096)`` with the paper's Table II
   per-core counts (250 neurons per PE, ~0.5 GB of int16 synaptic slabs
   on the device) -> ``compile`` -> ``ChipSim``, 256 ticks three times:
   ``exec_mode="dense"``, ``"auto"`` (event mode at this size), and
   ``"auto"`` with both Pallas NoC kernels forced.  Integer-valued
   records are bitwise equal across the three, the wave is still alive
   at the end, and the first 64 ticks match the same program run on the
   host CPU (integer-valued records bitwise, float records within
   1e-5 relative, see ``compare``).  The compiled tick takes the
   weights as arguments: its code stays under 64 MB.
2. **Board** — the 4x12-board hybrid farm (48 chips of 4x2 QPEs, 1536
   PEs) at the benchmark cell's widths (512 neurons per NEF PE, 64
   hidden units) -> ``compile_for_board`` -> ``ChipSim``, 64 ticks
   dense, auto, and sparse with the Pallas prefix-sum kernel, with the
   same checks.
3. **Serving** — ``FleetEngine`` on ``adaptive_scenario`` with the width
   ladder (16, 32, 64) serves 32 Poisson sessions to completion: none is
   dropped and the health verdict is not ``critical``.

Timings are printed as single readings of one run on the chip, for
orientation; they are not benchmark results.  The last line of standard
output is the JSON verdict.  The script exits non-zero, and prints no
verdict, when JAX's first device is not a TPU or when any check fails.
"""
from __future__ import annotations

import json
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

SYNFIRE_PES = 4096
SYNFIRE_TICKS = 256
BOARD, BOARD_CHIP = "4x12", "4x2"
BOARD_NEURONS, BOARD_HIDDEN = 512, 64
BOARD_TICKS = 64
CPU_TICKS = 64
FLEET_LEVELS = (16, 32, 64)
FLEET_SESSIONS = 32
CODE_LIMIT_BYTES = 64 << 20
RTOL = 1e-5
NOTE = "single run, not a benchmark"


def log(msg: str) -> None:
    print(msg, flush=True)


def to_host(recs: dict) -> dict:
    return {k: np.asarray(v) for k, v in recs.items()}


def integer_valued(x: np.ndarray) -> bool:
    return (not np.issubdtype(x.dtype, np.floating)
            or bool(np.all(x == np.round(x))))


def compare(name: str, ref: dict, got: dict) -> None:
    """Integer-valued records of ``ref`` (spikes, packets, link loads and
    flits, active sources) must match bitwise; float records within
    ``RTOL``: energies (``e_*``, positive sums) entry by entry, other
    float records (decoded values, activations, which may cancel to near
    zero) relative to the record's largest magnitude."""
    if set(ref) != set(got):
        raise AssertionError(f"{name}: record keys differ: "
                             f"{sorted(set(ref) ^ set(got))}")
    worst = 0.0
    for k in sorted(ref):
        a, b = ref[k], got[k]
        if a.shape != b.shape or a.dtype != b.dtype:
            raise AssertionError(f"{name}: {k} is {b.dtype}{b.shape}, "
                                 f"expected {a.dtype}{a.shape}")
        if integer_valued(a):
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"{name}: {k} differs in {int((a != b).sum())} of "
                    f"{a.size} entries")
        else:
            a64 = a.astype(np.float64)
            floor = 0.0 if k.startswith("e_") else np.abs(a64).max(initial=0)
            np.testing.assert_allclose(b, a, rtol=RTOL, atol=RTOL * floor,
                                       err_msg=f"{name}: {k}")
            den = np.maximum(np.abs(a64), max(floor, 1e-300))
            worst = max(worst, float((np.abs(b - a64) / den).max(initial=0)))
    log(f"  {name}: {len(ref)} records match "
        f"(float records max rel diff {worst:.3g})")


def timed_runs(sim, n_ticks: int, label: str) -> dict:
    """Run twice: the first call traces and compiles, the second is the
    steady state.  Returns the second call's records on the host."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(sim.run(n_ticks))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    recs = jax.block_until_ready(sim.run(n_ticks))
    steady_s = time.perf_counter() - t0
    log(f"  {label}: first call {first_s:.2f} s (trace + compile + run), "
        f"steady {steady_s / n_ticks * 1e6:.1f} us/tick [{NOTE}]")
    return to_host(recs)


def cpu_reference(program, n_ticks: int) -> dict:
    """The same program, dense, on the host CPU."""
    import jax
    from repro.chip.chip import ChipSim
    with jax.default_device(jax.devices("cpu")[0]):
        t0 = time.perf_counter()
        recs = to_host(ChipSim(program, exec_mode="dense").run(n_ticks))
    log(f"  cpu reference: {n_ticks} ticks in "
        f"{time.perf_counter() - t0:.2f} s")
    return recs


def check_code_size(sim, label: str) -> None:
    """The compiled tick carries the weights as arguments, not code."""
    import jax
    import jax.numpy as jnp
    init, step, params = sim.make_stepper()
    mem = jax.jit(step).lower(params, init, jnp.int32(0)) \
        .compile().memory_analysis()
    code = mem.generated_code_size_in_bytes
    log(f"  {label} tick program: code {code / 2**20:.2f} MiB, "
        f"arguments {mem.argument_size_in_bytes / 2**20:.1f} MiB")
    if code >= CODE_LIMIT_BYTES:
        raise AssertionError(f"{label}: generated code {code} B >= "
                             f"{CODE_LIMIT_BYTES} B")


def synfire_phase() -> None:
    from repro.chip.chip import ChipSim
    from repro.chip.compile import compile as compile_graph
    from repro.chip.workloads import synfire_graph
    log(f"synfire ring, {SYNFIRE_PES} PEs, {SYNFIRE_TICKS} ticks")
    t0 = time.perf_counter()
    program = compile_graph(synfire_graph(SYNFIRE_PES))
    net = program.graph.semantics.net
    log(f"  build + compile_graph {time.perf_counter() - t0:.1f} s; "
        f"synapse masks {net.synapse_bytes / 2**20:.1f} MiB")
    dense = ChipSim(program, exec_mode="dense")
    auto = ChipSim(program)
    pallas = ChipSim(program, link_load_impl="pallas", event_impl="pallas")
    if not auto.use_event_mode():
        raise AssertionError("auto did not resolve to event mode")
    runs = {"dense": timed_runs(dense, SYNFIRE_TICKS, "dense"),
            "auto": timed_runs(auto, SYNFIRE_TICKS, "auto (event)"),
            "pallas": timed_runs(pallas, SYNFIRE_TICKS,
                                 "auto + Pallas NoC kernels")}
    for mode in ("auto", "pallas"):
        compare(f"synfire {mode} vs dense", runs["dense"], runs[mode])
    tail = runs["dense"]["spikes_exc"][-32:].sum()
    if tail == 0:
        raise AssertionError("synfire wave died: no spikes in the last 32 "
                             "ticks")
    log(f"  wave alive: {int(tail)} exc spikes in the last 32 ticks")
    check_code_size(auto, "synfire auto")
    ref = cpu_reference(program, CPU_TICKS)
    compare("synfire tpu vs cpu", ref,
            {k: v[:CPU_TICKS] for k, v in runs["dense"].items()})


def board_phase() -> None:
    from repro.board import compile_for_board
    from repro.chip.chip import ChipSim
    from repro.chip.workloads import hybrid_farm_board_graph
    log(f"board {BOARD} of {BOARD_CHIP} chips, hybrid farm of "
        f"{BOARD_NEURONS} neurons and {BOARD_HIDDEN} hidden units a "
        f"channel, {BOARD_TICKS} ticks")
    t0 = time.perf_counter()
    program = compile_for_board(hybrid_farm_board_graph(
        BOARD, chip=BOARD_CHIP, n_neurons=BOARD_NEURONS,
        hidden=BOARD_HIDDEN))
    log(f"  build + compile_for_board {time.perf_counter() - t0:.1f} s")
    runs = {
        "dense": timed_runs(ChipSim(program, exec_mode="dense"),
                            BOARD_TICKS, "dense"),
        "auto": timed_runs(ChipSim(program), BOARD_TICKS, "auto"),
        "pallas": timed_runs(ChipSim(program, noc_mode="sparse",
                                     link_load_impl="pallas"),
                             BOARD_TICKS, "sparse + Pallas prefix sum"),
    }
    for mode in ("auto", "pallas"):
        compare(f"board {mode} vs dense", runs["dense"], runs[mode])
    if runs["dense"]["flits_xchip"].sum() <= 0:
        raise AssertionError("board: no chip-to-chip traffic")
    ref = cpu_reference(program, CPU_TICKS)
    compare("board tpu vs cpu", ref,
            {k: v[:CPU_TICKS] for k, v in runs["dense"].items()})


def serving_phase() -> None:
    from repro.core.dvfs import QueueDVFS
    from repro.serve.fleet import (FleetEngine, PoissonTraffic,
                                   adaptive_scenario)
    log(f"serving: adaptive scenario, widths {FLEET_LEVELS}, "
        f"{FLEET_SESSIONS} Poisson sessions")
    t0 = time.perf_counter()
    engine = FleetEngine(adaptive_scenario(), round_ticks=64,
                         dvfs=QueueDVFS(thresholds=(8, 16),
                                        batch_levels=FLEET_LEVELS),
                         keep_outputs=False, obs=True)
    setup_s = time.perf_counter() - t0
    out = engine.serve(PoissonTraffic(rate=8.0, n_sessions=FLEET_SESSIONS,
                                      tick_range=(128, 384), seed=0))
    st = out["stats"]
    health = out["obs"]["health"]
    log(f"  setup {setup_s:.1f} s; served {st['completed']} sessions in "
        f"{st['wall_s']:.1f} s over {st['rounds']} rounds (widths "
        f"{st['width_hist']}, compiles included) [{NOTE}]")
    log(f"  health {health['status']}, dropped "
        f"{health['dropped_sessions']}")
    if st["completed"] != FLEET_SESSIONS or health["dropped_sessions"]:
        raise AssertionError(f"fleet completed {st['completed']}/"
                             f"{FLEET_SESSIONS} sessions")
    if health["status"] == "critical":
        raise AssertionError(f"fleet health critical: {health}")


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU", file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}, jaxlib {metadata.version('jaxlib')}, libtpu "
        f"{metadata.version('libtpu')}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compilation_cache
    log(f"compilation cache: {enable_compilation_cache()}")
    for phase in (synfire_phase, board_phase, serving_phase):
        t0 = time.perf_counter()
        phase()
        log(f"  phase done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
