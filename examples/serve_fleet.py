"""Neuromorphic serving: a vmapped fleet of chip instances under user
traffic, width-elastic via the paper's spike-FIFO -> performance-level
loop (QueueDVFS).

    PYTHONPATH=src python examples/serve_fleet.py

Each user session streams a reference signal into its OWN instance of
the adaptive-control program (NEF ensemble + PES decoders tracking a
plant over the mesh); the fleet advances all resident sessions together
in one batched scan, admits from the shared request queue as bursts
arrive, and narrows — checkpointing evicted sessions — as it drains.
"""
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core.dvfs import QueueDVFS
from repro.serve.fleet import FleetEngine, PoissonTraffic, adaptive_scenario

enable_compilation_cache()

sc = adaptive_scenario(n_channels=1, n_neurons=64, learning_rate=1e-5)
eng = FleetEngine(sc, round_ticks=64,
                  dvfs=QueueDVFS(thresholds=(3, 8), batch_levels=(4, 8, 16)))
traffic = PoissonTraffic(rate=4.0, n_sessions=24, tick_range=(512, 1024),
                         seed=0)
out = eng.serve(traffic)
st = out["stats"]

print(f"served {st['completed']} sessions in {st['rounds']} rounds "
      f"({st['wall_s']:.1f}s wall, {st['sessions_per_s']:.1f} sessions/s)")
print(f"fleet widths used: {st['width_hist']} "
      f"(levels {eng.dvfs.batch_levels}, thresholds {eng.dvfs.thresholds})")
print(f"request latency p50/p99: {st['request_latency_s']['p50']:.2f}/"
      f"{st['request_latency_s']['p99']:.2f} s; "
      f"simulated {st['joules_per_request'] * 1e3:.2f} mJ/request; "
      f"{st['preemptions']} preemptions")

errs = np.array([[s.response["initial_err"], s.response["final_err"]]
                 for s in out["sessions"]])
print(f"per-session PES learning: mean |err| {errs[:, 0].mean():.3f} -> "
      f"{errs[:, 1].mean():.3f} over each session's stream")
print("burst -> wide fleet (PL3-like); drained queue -> narrow + checkpoint")
