"""Device trace of the measured window, and its reduction to numbers.

A traced run wraps its window in ``capture``; the driver drops a
``mark(WINDOW_START)`` and a ``mark(WINDOW_END)`` host annotation where
its window opens and closes, and host spans (``span``) around the calls
it makes into the program.  ``load`` reads the profiler's
``.xplane.pb``; ``reduce`` turns it into:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the chips used;
* ``window_s``: the window's length on the trace's own clock;
* ``device_ops``: the operations that took most device time;
* ``idle_gaps``: device idle time inside the window, summed by the
  innermost host span that covered each gap, so that a gap is named by
  what the host was doing.
"""
from __future__ import annotations

import contextlib
import glob
import os
import tempfile
from collections import defaultdict

import numpy as np

WINDOW_START = "bench.window_start"
WINDOW_END = "bench.window_end"
# device-time gaps shorter than this are summed as one entry: they are
# the spacing between the operations of one program, not host stalls
SHORT_GAP_NS = 10_000
DEVICE_LINES = ("XLA Ops", "XLA Modules")
EXECUTE = "PJRT_LoadedExecutable_Execute"


def mark(name: str) -> None:
    """A zero-length host annotation at this instant."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass


def span(name: str):
    """A host span around a call into the program (profiler-only cost)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the enclosed block when ``enabled``; yields a holder whose
    ``path`` is the ``.xplane.pb`` once the block has closed.  The trace
    is written under ``TMPDIR`` and deleted with the holder's directory
    when ``close()`` is called."""
    holder = _Trace()
    if not enabled:
        yield holder
        return
    import jax
    holder.dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
    # host events are JAX's own and the benchmark's spans: the Python
    # function tracer would slow every host loop it watches
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(holder.dir.name, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(holder.dir.name, "**", "*.xplane.pb"),
                          recursive=True)
        holder.path = found[0] if found else None


class _Trace:
    dir = None
    path = None

    def close(self) -> None:
        if self.dir is not None:
            self.dir.cleanup()
            self.dir = None


def load(path: str) -> dict:
    """Events of a profiler trace as plain arrays:

    ``{"devices": {plane: (names, start_ns, dur_ns)},
       "modules": {plane: start_ns of each program run},
       "host": {line: (names, start_ns, dur_ns)}}``

    A device plane contributes its ``XLA Ops`` line (its ``XLA Modules``
    line where it has no op line); host lines are the ``/host:CPU``
    plane's threads."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, modules, host = {}, {}, {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for want in DEVICE_LINES:
                if want in lines:
                    devices[plane.name] = _arrays(lines[want].events)
                    break
            if "XLA Modules" in lines:
                modules[plane.name] = _arrays(lines["XLA Modules"].events)[1]
        elif plane.name.startswith("/host:CPU"):
            for name, ln in lines.items():
                host[name] = _arrays(ln.events)
    return {"devices": devices, "modules": modules, "host": host}


def _arrays(events) -> tuple:
    names, start, dur = [], [], []
    for e in events:
        names.append(e.name)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    return (np.asarray(names, object), np.asarray(start, np.float64),
            np.asarray(dur, np.float64))


def _window(host: dict) -> tuple:
    """(start_ns, end_ns, host line) of the window marks."""
    for line, (names, start, _) in host.items():
        a = np.flatnonzero(names == WINDOW_START)
        b = np.flatnonzero(names == WINDOW_END)
        if a.size and b.size:
            return float(start[a[0]]), float(start[b[-1]]), line
    raise ValueError("the trace holds no window marks "
                     f"({WINDOW_START!r} ... {WINDOW_END!r})")


def _merge(start: np.ndarray, end: np.ndarray) -> tuple:
    """Union of intervals, as sorted disjoint (start, end) arrays."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx) if idx.size else e


def reduce(events: dict, n_top: int = 10, chips: int | None = None) -> dict:
    """Busy time, window length, top device ops and named idle gaps of
    the window (see the module docstring).  ``chips`` limits the device
    planes to the first ``chips`` (the chips the cell uses)."""
    w0, w1, host_line = _window(events["host"])
    planes = sorted(events["devices"])
    if chips is not None:
        planes = planes[:chips]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy, ops = [], defaultdict(float)
    first_gaps = None
    for plane in planes:
        names, start, dur = events["devices"][plane]
        start = start - _clock_offset(events.get("modules", {}).get(plane),
                                      events["host"])
        s = np.clip(start, w0, w1)
        e = np.clip(start + dur, w0, w1)
        keep = e > s
        for n, d in zip(names[keep], _self_time(s[keep], e[keep])):
            ops[_op_name(n)] += d
        ms, me = _merge(s[keep], e[keep])
        busy.append(float((me - ms).sum()))
        if first_gaps is None:
            edges_s = np.concatenate([[w0], me])
            edges_e = np.concatenate([ms, [w1]])
            g = edges_e > edges_s
            first_gaps = (edges_s[g], edges_e[g])
    gaps = _name_gaps(first_gaps, events["host"][host_line])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:n_top]
    return {
        "busy_s": float(np.mean(busy)) * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[str(n), v * 1e-9] for n, v in top_ops],
        "idle_gaps": [[n, v * 1e-9] for n, v in gaps[:n_top]],
    }


def _clock_offset(module_starts, host: dict) -> float:
    """How far the device's clock runs ahead of the host's, in ns.

    Each program run on the device (``XLA Modules``) has one host
    ``PJRT_LoadedExecutable_Execute`` that launched it; paired in order,
    the median difference is the offset (on one TPU v5e it was about
    -1.25 ms).  Runs cut at either end of the trace are allowed for by
    pairing from the start and from the end and keeping the tighter
    pairing.  No pairs: no correction."""
    execs = [start[names == EXECUTE] for names, start, _ in host.values()]
    execs = np.sort(np.concatenate(execs)) if execs else np.empty(0)
    if module_starts is None or not len(module_starts) or not execs.size:
        return 0.0
    m = np.sort(module_starts)
    n = min(m.size, execs.size)
    best = None
    for d in (m[:n] - execs[:n], m[-n:] - execs[-n:]):
        q1, q3 = np.percentile(d, [25, 75])
        if best is None or q3 - q1 < best[0]:
            best = (q3 - q1, float(np.median(d)))
    return best[1]


def _op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return str(hlo).split(" = ", 1)[0].lstrip("%")


def _self_time(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each op's duration less the ops nested inside it (a loop or a
    conditional holds the ops it runs)."""
    order = np.lexsort((-end, start))
    own = end - start
    stack: list = []
    for i in order:
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= end[i] - start[i]
        stack.append(i)
    return own


def _name_gaps(gaps: tuple, host: tuple) -> list:
    """Idle time summed by the innermost host span covering each gap's
    midpoint; gaps under ``SHORT_GAP_NS`` are one entry of their own."""
    gs, ge = gaps
    total: dict = defaultdict(float)
    short = (ge - gs) < SHORT_GAP_NS
    if short.any():
        total[f"gaps under {SHORT_GAP_NS // 1000} us"] += float(
            (ge - gs)[short].sum())
    gs, ge = gs[~short], ge[~short]
    mid = 0.5 * (gs + ge)                       # sorted: gaps are disjoint
    names, start, dur = host
    keep = (dur > 0) & (names != WINDOW_START) & (names != WINDOW_END)
    names, start, end = names[keep], start[keep], (start + dur)[keep]
    lo = np.searchsorted(mid, start, side="left")
    hi = np.searchsorted(mid, end, side="right")
    label = np.full(mid.size, "host: no span", dtype=object)
    # longest first, so the innermost span covering a midpoint names it
    for i in np.flatnonzero(hi > lo)[np.argsort(-(end - start)[hi > lo],
                                                kind="stable")]:
        label[lo[i]:hi[i]] = names[i]
    for name, g in zip(label, ge - gs):
        total[str(name)] += float(g)
    return sorted(total.items(), key=lambda kv: -kv[1])


def idle_share(window: dict):
    """1 - busy / window of a traced window, in %; None untraced."""
    tr = window.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
