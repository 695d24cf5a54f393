"""The trace reduction: busy time as a union of device op intervals
inside the window marks, idle gaps named by the innermost host span."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_window.xplane.pb"


def _ev(*rows):
    names = np.array([r[0] for r in rows], object)
    return (names, np.array([r[1] for r in rows], float),
            np.array([r[2] for r in rows], float))


def test_busy_is_the_union_inside_the_window():
    host = _ev((trace.WINDOW_START, 1_000, 0), ("bench.job", 1_000, 98_000),
               ("dispatch", 40_000, 30_000), (trace.WINDOW_END, 101_000, 0))
    ops = _ev(("early", 0, 3_000),             # 2 us inside the window
              ("%while.1 = (s32[]) while(...)", 10_000, 20_000),
              ("%fusion.2 = f32[8] fusion(...)", 15_000, 10_000),
              ("%dot = f32[8] dot(...)", 80_000, 5_000),
              ("late", 100_000, 5_000))
    r = trace.reduce({"devices": {"/device:TPU:0": ops},
                      "host": {"python": host}})
    assert r["window_s"] == pytest.approx(100e-6)
    # union: [1,3) + [10,30) + [80,85) + [100,101) us = 2 + 20 + 5 + 1
    assert r["busy_s"] == pytest.approx(28e-6)
    # self time: the loop holds the fusion nested inside it
    assert dict(r["device_ops"]) == pytest.approx(
        {"while.1": 10e-6, "fusion.2": 10e-6, "dot": 5e-6, "early": 2e-6,
         "late": 1e-6})
    gaps = dict(r["idle_gaps"])
    # gaps: [3,10) 7 us short; [30,80) 50 us under "dispatch" (mid 55);
    # [85,100) 15 us under "bench.job" only
    assert gaps["gaps under 10 us"] == pytest.approx(7e-6)
    assert gaps["dispatch"] == pytest.approx(50e-6)
    assert gaps["bench.job"] == pytest.approx(15e-6)
    assert trace.idle_share({"trace": r}) == pytest.approx(72.0)


def test_device_clock_is_aligned_to_the_host_by_program_launches():
    host = _ev((trace.WINDOW_START, 0, 0), (trace.EXECUTE, 10_000, 500),
               (trace.EXECUTE, 50_000, 500), (trace.WINDOW_END, 100_000, 0))
    ops = _ev(("a", 7_000, 1_000), ("b", 47_000, 2_000))
    r = trace.reduce({"devices": {"/device:TPU:0": ops},
                      "modules": {"/device:TPU:0": np.array([7e3, 47e3])},
                      "host": {"python": host}})
    assert r["busy_s"] == pytest.approx(3e-6)
    names = {n for n, _ in r["idle_gaps"]}
    assert names == {"host: no span"}


def test_no_window_marks_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"/device:TPU:0": _ev(("a", 0, 1))},
                      "host": {"python": _ev(("x", 0, 1))}})


def test_recorded_tpu_trace():
    """A window of two small jitted programs recorded on one TPU v5e:
    three ``bench.job`` spans, each two programs and a 2 ms sleep."""
    ev = trace.load(str(FIXTURE))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    r = trace.reduce(ev, chips=1)
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["device_ops"]] == ["fusion",
                                               "multiply_reduce_fusion"]
    # the device clock ran ~1.25 ms behind the host's: once aligned, all
    # six program runs fall inside the window marks
    assert r["busy_s"] == pytest.approx(3 * (11.136e-6 + 2.398e-6), rel=0.02)
    assert r["window_s"] > 0.006                  # three 2 ms sleeps
    assert 95 < trace.idle_share({"trace": r}) < 100


def test_tick_mfu_divides_by_the_trace_window():
    """``tick_mfu.sim`` on the recorded trace: the time per tick is the
    trace's own window over the ticks run in it, not the host's
    ``window_s``; untraced, it reads nothing."""
    from bench import spec
    from bench.work import least_time_s
    r = trace.reduce(trace.load(str(FIXTURE)), chips=1)
    peaks = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
             "hbm_bytes_per_s": 819e9}
    work = {"ops_per_tick": 2e6, "bytes_per_tick": 6e6}
    window = {"window_s": 1.0, "ticks": 3, "peaks": peaks, "work": work,
              "trace": r}
    reader = spec.load_metric("tick_mfu.sim")
    least = least_time_s(2e6, 6e6, peaks)
    assert least == pytest.approx(6e6 / 819e9)
    assert reader.read(window) == pytest.approx(
        100 * least / (r["window_s"] / 3))
    assert reader.read(dict(window, window_s=50.0)) == reader.read(window)
    assert reader.read({k: v for k, v in window.items()
                        if k != "trace"}) is None
