"""Share of the chip's peak a simulated tick reaches, in %: the least
time the tick's needed work could take on this chip
(``bench.work.least_time_s`` of the counts in ``bench.work``) over the
time per tick of the traced window, on the profiler trace's own clock
(the window marks' distance over the ticks of the jobs run between
them)."""
from bench.work import least_time_s


def read(window: dict):
    work, tr = window.get("work"), window.get("trace")
    if not work or not tr or not window.get("ticks") or tr["window_s"] <= 0:
        return None
    least = least_time_s(work["ops_per_tick"], work["bytes_per_tick"],
                         window["peaks"])
    return 100.0 * least / (tr["window_s"] / window["ticks"])
