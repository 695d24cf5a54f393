"""Telemetry subsystem (repro.obs): probe neutrality goldens, probe
correctness, link-profile parity, Perfetto trace export, manifest
provenance, and the bench regression gate.

Acceptance anchors:

* probes-off runs are BITWISE identical to the pre-telemetry engine —
  the synfire golden still reproduces ``simulate_synfire`` through the
  default ``run()`` path, and a plastic 2x2-board run's records do not
  change whether probes are compiled into the carry or not;
* the whole-run link probes reproduce the pre-probe ``--profile-links``
  JSON schema exactly (peak/mean off the full-resolution records);
* a 2x2-board run exports trace-event JSON with per-PE and per-tier
  tracks that round-trips through ``json``;
* ``repro.obs.report`` exits nonzero on an injected >20% tick_us
  regression and 0 within threshold / with ``--warn-only``.
"""
import json

import numpy as np
import pytest

from repro.board import BoardSpec, compile_board
from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import hybrid_farm_board_graph, synfire_graph
from repro.core.snn import build_synfire, simulate_synfire
from repro.learn.adaptive import adaptive_control_graph
from repro.obs import (ProbeSpec, bench_payload, default_probes,
                       link_profile, link_profile_probes,
                       record_link_profile, run_manifest, trace_events,
                       write_trace)
from repro.obs.report import diff_benches, main as report_main
from repro.obs.trace import main as trace_main


def _assert_same_records(a: dict, b: dict, keys=None, float_ulps=0):
    """Records equal bitwise; with ``float_ulps`` > 0, floating-point
    records may differ by that many units in the last place."""
    for k in (keys or a):
        if k == "probes":
            continue
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if float_ulps and np.issubdtype(x.dtype, np.floating):
            np.testing.assert_array_max_ulp(x, y, maxulp=float_ulps)
        else:
            assert np.array_equal(x, y), k


# -------------------------------------------------------------------------
# Probe neutrality: probes-off == pre-telemetry engine, bitwise
# -------------------------------------------------------------------------

def test_probes_off_golden_synfire_vs_seed_engine():
    """The default ``run()`` (zero probes) still traces the pre-PR tick
    body: the 8-PE synfire golden reproduces ``simulate_synfire`` bit
    for bit, and ``probes=()`` is the very same path."""
    sim = ChipSim(compile_graph(synfire_graph(8)))
    recs = sim.run(300)
    ref = simulate_synfire(build_synfire(0), 300)
    for k in ref:
        assert np.array_equal(np.asarray(recs[k]), np.asarray(ref[k])), k
    _assert_same_records(sim.run(300, probes=()), recs)
    assert "probes" not in recs


def test_probed_run_leaves_records_bitwise_identical():
    """Probes only read the tick's records — compiling them into the
    carry must not perturb a single bit of the integer records.  A
    float32 energy may move by one ulp: the probed program is a
    different XLA program, which may fuse (and contract to FMA) the
    Eq. (1) multiply-adds differently; a probe writing state would move
    the integer records too."""
    sim = ChipSim(compile_graph(synfire_graph(8)))
    bare = sim.run(300)
    probed = sim.run(300, probes=default_probes(sim.program))
    _assert_same_records(bare, probed, keys=bare, float_ulps=1)
    assert set(probed["probes"]) >= {"link_flits_peak", "pe_pl_mean",
                                     "pe_packets_sum", "e_noc_sum"}


# 2x2 board, 1x1-QPE chips: 4 channels don't fit on one chip, so the
# plastic control loops are forced across the SerDes tier
BOARD_KW = dict(n_channels=4, n_neurons=50, n_ticks=128, period=128)


@pytest.fixture(scope="module")
def plastic_board_sim():
    board = BoardSpec.parse("2x2", chip="1x1")
    g = adaptive_control_graph(**BOARD_KW)
    return ChipSim(compile_board(g, board, refine=False))


def test_probes_off_golden_board_plastic(plastic_board_sim):
    """A plastic 2x2-board run (cross-chip learning traffic) records
    identically with and without probes in the scan carry."""
    sim = plastic_board_sim
    bare = sim.run(128)
    assert float(np.asarray(bare["flits_xchip"]).sum()) > 0
    assert "e_learn" in bare
    probed = sim.run(128, probes=default_probes(sim.program))
    _assert_same_records(bare, probed, keys=bare)
    # the learn tier is probed too: per-slot |dw| plus per-PE e_learn
    assert "pe_e_learn_sum" in probed["probes"]
    assert any(k.startswith("learn_dw_") for k in probed["probes"])


# -------------------------------------------------------------------------
# Probe semantics: registry, validation, keep_records
# -------------------------------------------------------------------------

def test_probe_registry_and_validation():
    sim = ChipSim(compile_graph(synfire_graph(8)))
    # registry names expand to specs
    recs = sim.run(32, probes=("link_flits", "dvfs"))
    assert {"link_flits_peak", "link_flits_mean", "pe_pl_mean",
            "pe_pl_ema"} == set(recs["probes"])
    with pytest.raises(ValueError, match="unknown probe set"):
        sim.run(8, probes=("no_such_set",))
    with pytest.raises(KeyError, match="available keys"):
        sim.run(8, probes=(ProbeSpec("x", "no_such_rec_key", "peak"),))
    with pytest.raises(ValueError, match="duplicate probe names"):
        sim.run(8, probes=(ProbeSpec("x", "pl", "peak"),
                           ProbeSpec("x", "pl", "mean")))
    with pytest.raises(ValueError, match="unknown op"):
        ProbeSpec("x", "pl", "median")
    with pytest.raises(ValueError, match="keep_records"):
        sim.run(8, keep_records=False)


def test_keep_records_false_returns_only_probes():
    """The memory-bounded mode: strided probe buffers, no (T, ...)
    records — and the probe values match the full-resolution run."""
    sim = ChipSim(compile_graph(synfire_graph(8)))
    full = sim.run(300)
    slim = sim.run(300, probes=(ProbeSpec("pk", "link_flits", "peak"),),
                   keep_records=False)
    assert set(slim) == {"probes"}
    np.testing.assert_array_equal(
        np.asarray(slim["probes"]["pk"])[-1],
        np.asarray(full["link_flits"]).max(axis=0))


# -------------------------------------------------------------------------
# Link-profile parity: probe-based profiles == the pre-probe schema
# -------------------------------------------------------------------------

def test_link_profile_parity_chip_and_board(plastic_board_sim):
    """``record_link_profile`` must emit the exact JSON the benchmarks'
    hand-rolled ``--profile-links`` paths used to: per-link peak/mean
    flits off the full-resolution records, tier boundary included."""
    for sim, n_ticks in ((ChipSim(compile_graph(synfire_graph(16))), 64),
                         (plastic_board_sim, 128)):
        flits = np.asarray(sim.run(n_ticks)["link_flits"])
        legacy = {
            "n_onchip_links": int(sim.program.noc.n_onchip_links),
            "peak": np.round(flits.max(axis=0), 2).tolist(),
            "mean": np.round(flits.mean(axis=0), 4).tolist(),
        }
        assert record_link_profile(sim, n_ticks) == legacy


def test_link_profile_formats_probe_output():
    sim = ChipSim(compile_graph(synfire_graph(8)))
    recs = sim.run(64, probes=link_profile_probes(), keep_records=False)
    prof = link_profile(sim.program, recs["probes"])
    assert prof["n_onchip_links"] == sim.program.noc.n_links
    assert len(prof["peak"]) == len(prof["mean"]) == sim.program.noc.n_links


# -------------------------------------------------------------------------
# Perfetto trace export
# -------------------------------------------------------------------------

def test_trace_events_board(plastic_board_sim, tmp_path):
    sim = plastic_board_sim
    recs = sim.run(128)
    payload = trace_events(sim.program, recs)
    ev = payload["traceEvents"]
    # per-tier NoC counters (on-chip AND the SerDes tier)
    counters = {e["name"] for e in ev if e["ph"] == "C"}
    assert {"flits/onchip", "flits/xchip"} <= counters
    # learn tier: per-slot |dw| counters
    assert any(n.startswith("dw ") for n in counters)
    # per-PE threads grouped into per-chip processes
    procs = {e["args"]["name"] for e in ev
             if e.get("name") == "process_name"}
    assert sum(p.startswith("chip ") for p in procs) >= 2
    threads = [e for e in ev if e.get("name") == "thread_name"]
    assert len(threads) == sim.program.n_pes
    # per-PE DVFS counter tracks + active-tick slices
    assert any(n.startswith("pl PE") for n in counters)
    slices = [e for e in ev if e["ph"] == "X"]
    assert slices and all(
        {"pid", "tid", "ts", "dur", "name"} <= set(e) for e in slices)
    assert all(e["ts"] >= 0 and e["dur"] > 0 for e in slices)
    # round-trips through json and the file writer
    path = write_trace(tmp_path / "t.perfetto-trace.json", sim.program,
                       recs)
    assert json.loads(path.read_text())["traceEvents"]


def test_trace_events_single_chip():
    sim = ChipSim(compile_graph(synfire_graph(8)))
    payload = trace_events(sim.program, sim.run(64))
    counters = {e["name"] for e in payload["traceEvents"]
                if e["ph"] == "C"}
    assert "flits/onchip" in counters and "flits/xchip" not in counters


def test_trace_cli_writes_artifact(tmp_path):
    out = tmp_path / "board.perfetto-trace.json"
    assert trace_main(["--board", "2x2", "--chip", "4x2", "--ticks", "8",
                       "--out", str(out)]) == 0
    payload = json.loads(out.read_text())
    assert payload["traceEvents"]


# -------------------------------------------------------------------------
# Manifest + regression report
# -------------------------------------------------------------------------

def test_manifest_and_bench_payload():
    man = run_manifest(seed=7, config={"a": 1})
    assert man["seed"] == 7 and man["config_hash"]
    assert man["jax_version"] and man["python"] and man["host"]
    rows = [{"name": "r", "us_per_call": 1.0, "derived": "",
             "values": {}}]
    payload = bench_payload(rows, link_profiles={"r": {}},
                            timers={"r": {"build": 0.1}})
    assert payload["manifest"]["jax_version"] == payload["jax_version"]
    assert payload["phase_timers"] == {"r": {"build": 0.1}}
    # different configs hash differently, same config stably
    a = run_manifest(config={"x": 1})["config_hash"]
    assert a == run_manifest(config={"x": 1})["config_hash"]
    assert a != run_manifest(config={"x": 2})["config_hash"]


def _payload(tick_us: float, compile_s: float = 1.0) -> dict:
    return bench_payload([{
        "name": "scale_hybrid_1024pe", "us_per_call": tick_us,
        "derived": f"compile_s={compile_s}",
        "values": {"compile_s": compile_s},
    }])


def test_report_gate_exit_codes(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_payload(100.0)))

    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_payload(110.0)))        # +10% — within 20%
    assert report_main([str(base), str(ok)]) == 0

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_payload(125.0)))       # +25% — regression
    assert report_main([str(base), str(bad)]) == 1
    assert report_main([str(base), str(bad), "--warn-only"]) == 0
    assert report_main([str(base), str(bad), "--threshold", "0.5"]) == 0
    # alternate metric off the parsed derived values
    slow_compile = tmp_path / "slow.json"
    slow_compile.write_text(json.dumps(_payload(100.0, compile_s=3.0)))
    assert report_main([str(base), str(slow_compile),
                        "--metric", "compile_s"]) == 1
    # malformed / incomparable inputs
    assert report_main([str(tmp_path / "missing.json"), str(ok)]) == 2
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"rows": []}))
    assert report_main([str(base), str(empty)]) == 2


def test_diff_benches_matches_rows_by_name():
    base = _payload(100.0)
    new = _payload(130.0)
    new["rows"].append({"name": "only_new", "us_per_call": 1.0,
                        "derived": "", "values": {}})
    base["rows"].append({"name": "only_base", "us_per_call": 1.0,
                        "derived": "", "values": {}})
    d = diff_benches(base, new)
    assert [r["name"] for r in d["regressions"]] == ["scale_hybrid_1024pe"]
    assert d["missing"] == ["only_base"]
    assert d["regressions"][0]["ratio"] == pytest.approx(1.3)


# -------------------------------------------------------------------------
# Overhead guard: the default probe set stays cheap in traced-op terms
# -------------------------------------------------------------------------

def test_board_probe_run_matches_hybrid_board_golden():
    """The full board pipeline (hybrid farm) through a probed run: the
    per-tier probe sums agree with the full-resolution records."""
    board = BoardSpec.parse("2x2", chip="2x2")
    prog = compile_board(hybrid_farm_board_graph(board), board)
    sim = ChipSim(prog)
    recs = sim.run(32, probes=(
        ProbeSpec("xf", "flits_xchip", "sum"),
        ProbeSpec("en", "e_noc", "sum"),
    ))
    np.testing.assert_allclose(
        np.asarray(recs["probes"]["xf"])[-1],
        np.asarray(recs["flits_xchip"]).sum(), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(recs["probes"]["en"])[-1],
        np.asarray(recs["e_noc"]).sum(), rtol=1e-5)
