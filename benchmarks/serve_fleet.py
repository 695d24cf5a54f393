"""Serving-tier benchmark: vmapped board fleets under user traffic —
the numbers behind BENCH_pr7.json.

Per row, one ``FleetEngine`` serves a Poisson arrival stream of user
sessions end-to-end (admission queue -> QueueDVFS width -> vmapped tick
scan -> streamed outputs -> completion), and reports:

* **throughput** — sessions/sec and instance-ticks/sec at the wall;
* **latency** — p50/p99 request latency (submit -> completion, queue
  wait included) and p50/p99 per-tick wall latency of the batched scan;
* **energy** — simulated joules/request (Eq. (1) DVFS datapath + NoC
  traffic + learning engine, summed over each session's ticks);
* **elasticity** — the width histogram and preemption count the
  spike-FIFO -> performance-level scheduling produced under the burst
  pattern.

The headline rows run a >= 64-instance fleet on both served scenarios
(adaptive control with per-session PES learning, and the KWS hybrid
farm).  ``--fleet`` scales the whole grid down for CI smoke runs.
"""
from __future__ import annotations

import time

from benchmarks.common import RESULTS, emit
from repro.core.dvfs import QueueDVFS
from repro.serve.fleet import FleetEngine, PoissonTraffic, SCENARIOS


def _dvfs_for(fleet: int) -> QueueDVFS:
    """Batch levels at fleet/4, fleet/2, fleet; thresholds scale with
    the levels so bursts actually climb the ladder."""
    lo = max(1, fleet // 4)
    mid = max(1, fleet // 2)
    return QueueDVFS(thresholds=(max(2, lo // 2), max(3, mid // 2)),
                     batch_levels=(lo, mid, fleet))


def bench_fleet(scenario: str, fleet: int, n_sessions: int, rate: float,
                round_ticks: int, tick_range: tuple, seed: int = 0,
                board: str | None = None, chip: str = "2x2",
                obs: bool = False, span_log: str | None = None) -> dict:
    """One fleet-serve row.  With ``obs`` the engine runs fully
    instrumented (spans + metrics + SLO monitor): the row name gains an
    ``_obs`` suffix (so off/on pairs coexist in one artifact and the
    obs overhead is a row-ratio), the metrics snapshot is merged into
    the row's ``values``, the span log optionally lands at ``span_log``
    and a ``critical`` health verdict fails the benchmark."""
    if scenario == "adaptive":
        sc = SCENARIOS[scenario](n_channels=1, n_neurons=64)
    else:
        sc = SCENARIOS[scenario](n_pairs=1, n_neurons=64, hidden=16)
    bd = None
    if board is not None:
        from repro.board import BoardSpec
        bd = BoardSpec.parse(board, chip=chip)
    eng = FleetEngine(sc, round_ticks=round_ticks, dvfs=_dvfs_for(fleet),
                      board=bd, keep_outputs=False, obs=obs)
    tr = PoissonTraffic(rate=rate, n_sessions=n_sessions,
                        tick_range=tick_range, seed=seed)
    t0 = time.perf_counter()
    out = eng.serve(tr)
    wall_s = time.perf_counter() - t0
    st = out["stats"]
    if st["completed"] != n_sessions:
        raise RuntimeError(f"fleet served {st['completed']}/{n_sessions} "
                           "sessions — the stream must drain completely")

    where = f"board{board}" if board else "chip"
    name = f"serve_fleet_{scenario}_{where}_w{fleet}" + \
        ("_obs" if obs else "")
    tick_p50_us = st["tick_latency_s"]["p50"] * 1e6
    widths = ",".join(f"{k}:{v}" for k, v in st["width_hist"].items())
    emit(name, tick_p50_us,
         f"fleet={fleet};sessions={n_sessions};rate={rate};"
         f"round_ticks={round_ticks};pes={eng.program.n_pes};"
         f"sessions_per_s={st['sessions_per_s']:.3f};"
         f"ticks_per_s={st['ticks_per_s']:.0f};"
         f"req_p50_s={st['request_latency_s']['p50']:.4f};"
         f"req_p99_s={st['request_latency_s']['p99']:.4f};"
         f"tick_p99_us={st['tick_latency_s']['p99'] * 1e6:.1f};"
         f"joules_per_request={st['joules_per_request']:.6f};"
         f"preemptions={st['preemptions']};rounds={st['rounds']};"
         f"queue_wait_p99_s={st['queue']['wait_p99_s']:.4f};"
         f"widths={widths};wall_s={wall_s:.2f}")

    if obs:
        o = out["obs"]
        row = RESULTS[-1]
        # metrics snapshot joins the row's machine-readable values (the
        # derived-string keys win on collision — e.g. sessions_per_s is
        # the whole-serve figure there, the last-round gauge here)
        for k, v in o["metrics"].items():
            row["values"].setdefault(k, v)
        row["values"]["health"] = o["health"]["status"]
        if span_log:
            p = o["spans"].write(span_log)
            print(f"# span log ({len(o['spans'].events)} events) -> {p}")
        if o["health"]["status"] == "critical":
            raise RuntimeError(f"fleet health CRITICAL: {o['health']}")
    return st


def main(fleet: int = 64, sessions: int = 96, rate: float = 8.0,
         round_ticks: int = 64, min_ticks: int = 128, max_ticks: int = 384,
         board: str | None = None, budget_s: float | None = None,
         obs: str = "off", span_log: str | None = None) -> None:
    t0 = time.perf_counter()
    tick_range = (min_ticks, max_ticks)
    for with_obs in {"off": (False,), "on": (True,),
                     "both": (False, True)}[obs]:
        # the span-log artifact comes from the first instrumented run
        slog = span_log if with_obs else None
        bench_fleet("adaptive", fleet, sessions, rate, round_ticks,
                    tick_range, obs=with_obs, span_log=slog)
        bench_fleet("kws", fleet, sessions, rate, round_ticks, tick_range,
                    seed=1, obs=with_obs)
        if board:
            bench_fleet("adaptive", max(1, fleet // 8),
                        max(4, sessions // 8), rate, round_ticks,
                        tick_range, seed=2, board=board, obs=with_obs)
    wall = time.perf_counter() - t0
    if budget_s is not None and wall > budget_s:
        raise RuntimeError(f"serve_fleet benchmark took {wall:.1f}s "
                           f"> budget {budget_s:.1f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fleet", type=int, default=64,
                    help="top batch level (>= 64 for the headline rows)")
    ap.add_argument("--sessions", type=int, default=96)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="expected session arrivals per scheduling round")
    ap.add_argument("--round-ticks", type=int, default=64)
    ap.add_argument("--min-ticks", type=int, default=128)
    ap.add_argument("--max-ticks", type=int, default=384)
    ap.add_argument("--board", default=None,
                    help="also run a board-compiled fleet row, e.g. 2x1")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if the whole run exceeds this many seconds")
    ap.add_argument("--obs", choices=("off", "on", "both"), default="off",
                    help="serve uninstrumented, instrumented (spans + "
                         "metrics + SLO gate, rows suffixed _obs), or "
                         "both back to back (overhead as a row pair)")
    ap.add_argument("--span-log", default=None, metavar="PATH",
                    help="write the first instrumented run's span log "
                         "here (.json / .json.gz)")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    main(fleet=args.fleet, sessions=args.sessions, rate=args.rate,
         round_ticks=args.round_ticks, min_ticks=args.min_ticks,
         max_ticks=args.max_ticks, board=args.board,
         budget_s=args.budget_s, obs=args.obs, span_log=args.span_log)

    if args.json:
        from repro.obs import write_bench_json
        write_bench_json(args.json, RESULTS, config=vars(args))
