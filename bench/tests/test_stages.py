"""Device time by named tick stage: ops looked up in the program's stage
table by the program run they started in, self time inside the window,
and the runs of a branch."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import stages, trace

FIXTURES = Path(__file__).parent / "fixtures"
SYN = "chip_tick/semantics/synapse"
TABLE = {"jit_scan": {"cond.1": SYN, "dot.2": f"{SYN}/dense_fallback",
                      "add.3": f"{SYN}/dense_fallback",
                      "gather.4": f"{SYN}/compressed",
                      "fusion.5": "chip_tick/noc",
                      "fusion.6": "chip_tick/learn"}}


def _ev(*rows):
    return (np.array([r[0] for r in rows], object),
            np.array([r[1] for r in rows], float),
            np.array([r[2] for r in rows], float))


def _window():
    """Five ticks of one ``jit_scan`` run inside a loop: a conditional
    (synapse) holding the dense fallback on ticks 0, 2, 4 (a dot, then
    two adds) and the compressed branch on ticks 1, 3; a NoC fusion per
    tick; then one op of another program, and one outside the window."""
    ops, t = [("%while.0 = (s32[]) while(...)", 1_000, 60_000)], 2_000
    for tick in range(5):
        ops.append(("%cond.1 = s32[8] conditional(...)", t, 8_000))
        if tick % 2 == 0:
            ops += [("%dot.2 = s32[8] dot(...)", t + 1_000, 4_000),
                    ("%add.3 = s32[8] add(...)", t + 5_000, 1_000),
                    ("%add.3 = s32[8] add(...)", t + 6_000, 1_000)]
        else:
            ops.append(("%gather.4 = s32[8] gather(...)", t + 1_000, 2_000))
        ops.append(("%fusion.5 = f32[4] fusion(...)", t + 8_000, 2_000))
        t += 11_000
    ops += [("%fusion.5 = f32[] fusion(...)", 80_000, 3_000),
            ("%fusion.5 = f32[] fusion(...)", 120_000, 3_000)]
    host = _ev((trace.WINDOW_START, 0, 0), (trace.WINDOW_END, 100_000, 0))
    modules = _ev(("jit_scan(42)", 500, 61_000), ("jit_other(7)", 79_000,
                                                   5_000))
    return {"devices": {"/device:TPU:0": _ev(*ops)},
            "module_runs": {"/device:TPU:0": modules},
            "host": {"python": host}}


def test_self_time_branch_runs_and_unscoped():
    s = stages.reduce(_window(), TABLE)
    us = {p: v["self_s"] * 1e6 for p, v in s.items()}
    runs = {p: v["runs"] for p, v in s.items()}
    # the conditional's own time: 8 us a tick less the branch it ran
    assert us[SYN] == pytest.approx(3 * 2 + 2 * 6)
    assert us[f"{SYN}/dense_fallback"] == pytest.approx(3 * 6)
    assert us[f"{SYN}/compressed"] == pytest.approx(2 * 2)
    assert us["chip_tick/noc"] == pytest.approx(5 * 2)
    # the loop's own time (60 - 5 * 10) and the other program's op
    assert us[stages.UNSCOPED] == pytest.approx(10 + 3)
    # a branch's runs are the count of its least frequent op (the dot,
    # not the add it runs twice); a stage that never ran reads 0
    assert runs[f"{SYN}/dense_fallback"] == 3
    assert runs[f"{SYN}/compressed"] == 2
    assert runs[SYN] == runs["chip_tick/noc"] == 5
    assert us["chip_tick/learn"] == 0 and runs["chip_tick/learn"] == 0
    busy = trace.reduce(_window())["busy_s"] * 1e6
    assert sum(us.values()) == pytest.approx(busy)


def test_no_table_entry_is_unscoped():
    s = stages.reduce(_window(), {})
    assert list(s) == [stages.UNSCOPED]
    assert s[stages.UNSCOPED]["self_s"] == pytest.approx(
        trace.reduce(_window())["busy_s"])


def test_recorded_tpu_trace_by_stage():
    """Two 30-tick jobs of a 256-PE synfire ring under the Gaussian
    background in event mode, recorded on one TPU v5e (stripped of the
    HLO plane and per-event stats), with the program's stage table:
    the fallback branch ran exactly on the ticks whose input set the
    records show overflowing, the compressed branch on the others."""
    meta = json.loads((FIXTURES / "tpu_stages.json").read_text())
    events = stages.load(str(FIXTURES / "tpu_stages.xplane.pb"))
    s = stages.reduce(events, meta["table"], chips=1)
    fallback = s[f"{SYN}/dense_fallback"]["runs"]
    assert fallback == meta["overflow_ticks"] == 42
    assert fallback + s[f"{SYN}/compressed"]["runs"] == meta["ticks"]
    busy = trace.reduce(events, chips=1)["busy_s"]
    assert sum(v["self_s"] for v in s.values()) == pytest.approx(busy)
    # the dense fallback's einsums are most of the device time here too
    assert s[f"{SYN}/dense_fallback"]["self_s"] > 0.5 * busy
