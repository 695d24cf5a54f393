"""Device time of the program's named tick stages in a traced window.

The program records where each compiled program put the named stages of
its tick (``repro.obs.scopes``: ``{HLO module: {instruction: stage
path}}``).  A device op in the profiler trace names its instruction, and
the program run (``XLA Modules`` event) it started in names its module.
``reduce`` sums the ops' self time inside the window marks by stage
path, on the clock and window of ``bench.trace.reduce``:

``{path: {"self_s": ..., "runs": ...}}`` where ``runs`` is the event
count of the stage's least frequent op (a branch's ops run once each
time the branch is taken; an op XLA hoisted out of the tick loop runs
once a job, so ``runs`` counts ticks only for a branch).  Device time of
ops in no stage is under ``unscoped``; a stage of a module that ran but
whose ops never did (a branch never taken) reads 0.

Nothing in ``bench.run`` calls this yet: ``bench.trace.reduce`` would
have to return it for a per-layer metric to read it (``PERF.md``,
section 7).
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from bench import trace

UNSCOPED = "unscoped"


def program_table() -> dict | None:
    """The program's ``{module: {instruction: stage path}}``, or None
    where the program records no stages."""
    try:
        from repro.obs.scopes import table
    except ImportError:
        return None
    return table()


def load(path: str) -> dict:
    """``bench.trace.load`` of a trace, plus ``module_runs``: each device
    plane's ``XLA Modules`` events as ``(names, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData
    events = trace.load(path)
    events["module_runs"] = {
        plane.name: trace._arrays(line.events)
        for plane in ProfileData.from_file(path).planes
        if plane.name in events["devices"]
        for line in plane.lines if line.name == "XLA Modules"}
    return events


def reduce(events: dict, table: dict, chips: int | None = None) -> dict:
    """Self time and runs of each stage path inside the window, averaged
    over the first ``chips`` device planes (see the module docstring)."""
    w0, w1, _ = trace._window(events["host"])
    planes = sorted(events["devices"])[:chips]
    per_plane = []
    for plane in planes:
        names, dev_start, dur = events["devices"][plane]
        start = dev_start - trace._clock_offset(
            events.get("modules", {}).get(plane), events["host"])
        s = np.clip(start, w0, w1)
        e = np.clip(start + dur, w0, w1)
        keep = e > s
        per_plane.append(_stage_times(
            names[keep], dev_start[keep], trace._self_time(s[keep], e[keep]),
            events.get("module_runs", {}).get(plane), table))
    paths = sorted(set().union(*per_plane))
    return {p: {"self_s": 1e-9 * float(np.mean(
                    [st.get(p, (0, 0))[0] for st in per_plane])),
                "runs": float(np.mean(
                    [st.get(p, (0, 0))[1] for st in per_plane]))}
            for p in paths}


def _stage_times(names, start, own, module_runs, table: dict) -> dict:
    """``{stage path: (self ns, runs)}`` of one device plane's ops: each
    op is looked up in ``table`` by the program run it started in and
    its instruction name."""
    module = np.full(names.size, "", object)    # "": in no program run
    if module_runs is not None and len(module_runs[0]):
        mnames, ms, md = module_runs
        order = np.argsort(ms, kind="stable")
        ms, me = ms[order], (ms + md)[order]
        # "jit_scan(1234...)" -> "jit_scan", the HLO module's name
        base = np.array([str(n).split("(", 1)[0] for n in mnames[order]],
                        object)
        i = np.searchsorted(ms, start, side="right") - 1
        inside = (i >= 0) & (start < me[np.maximum(i, 0)])
        module[inside] = base[i[inside]]
    self_ns, count = defaultdict(float), defaultdict(int)
    for key, d in zip(zip(module, names), own):
        self_ns[key] += d
        count[key] += 1
    times: dict = defaultdict(float)
    least: dict = {}                     # (module, path) -> fewest events
    for m in set(module) - {""}:
        for path in set(table.get(m, {}).values()):
            times[path] += 0.0
            least[m, path] = 0
    for (m, n), t in self_ns.items():
        path = table.get(m, {}).get(trace._op_name(n), UNSCOPED)
        times[path] += t
        seen = least.get((m, path))
        least[m, path] = count[m, n] if not seen else min(seen, count[m, n])
    runs: dict = defaultdict(int)
    for (_, path), n in least.items():
        runs[path] += n
    return {path: (times[path], runs[path]) for path in times}
