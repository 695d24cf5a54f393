"""Named tick stages and host spans of ``ChipSim.run``: every stage of
the tick reaches the compiled program's op metadata (and the table a
trace reduction reads), ``run`` shows as ``chip.*`` host spans under a
profiler trace, each program compiles once, and the records keep
exactly their keys."""
import glob

import jax
import pytest

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import synfire_graph
from repro.learn.adaptive import adaptive_control_graph
from repro.obs import scopes

SEMANTICS = {"fifo", "synapse", "background", "neuron", "route"}
ENGINE = {"chip_tick", "semantics", "noc"}
BRANCHES = {"compressed", "dense_fallback"}

# the 8-PE synfire records as before the stages were named
SYNFIRE_KEYS = {
    "pl", "n_fifo", "syn_events", "packets", "spikes_exc", "spikes_inh",
    "e_dvfs_baseline", "e_dvfs_neuron", "e_dvfs_synapse", "t_sp",
    "e_pl3_baseline", "e_pl3_neuron", "e_pl3_synapse", "link_load",
    "link_flits", "e_noc", "active_sources", "active_frac",
    "touched_links", "touched_links_onchip"}


@pytest.fixture(scope="module")
def sim8():
    return ChipSim(compile_graph(synfire_graph(8)))


def _stages_of_last_scan() -> set:
    return {part for path in scopes.table()["jit_scan"].values()
            for part in path.split("/")}


@pytest.mark.parametrize("mode, want", [
    ("dense", ENGINE | SEMANTICS),
    ("event", ENGINE | SEMANTICS | BRANCHES)])
def test_compiled_run_holds_every_stage(sim8, mode, want):
    recs = sim8.run(12, exec_mode=mode)
    assert set(recs) == SYNFIRE_KEYS
    assert _stages_of_last_scan() == want


def test_branch_paths_nest_under_synapse(sim8):
    sim8.run(12, exec_mode="event")
    paths = set(scopes.table()["jit_scan"].values())
    assert {"chip_tick/semantics/synapse/compressed",
            "chip_tick/semantics/synapse/dense_fallback",
            "chip_tick/noc"} <= paths
    assert all(p.startswith("chip_tick/") for p in paths)


def test_plastic_program_names_its_learn_stage():
    g = adaptive_control_graph(n_channels=1, n_neurons=16, n_ticks=8)
    ChipSim(compile_graph(g)).run(8)
    assert {"chip_tick", "semantics", "learn", "noc"} <= \
        _stages_of_last_scan()


def test_stage_path_keeps_only_stage_names():
    assert scopes.stage_path(
        "jit(scan)/while/body/closed_call/chip_tick/semantics/synapse/cond/"
        "branch_0_fun/dense_fallback/dot_general") == \
        "chip_tick/semantics/synapse/dense_fallback"
    assert scopes.stage_path("jit(scan)/while") == ""


def _host_events(run, tmp_path) -> list:
    """(name, stats) of the ``chip.*`` host events while ``run()`` ran
    under a profiler trace, in order of start."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        run()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                found += [(e.start_ns, e.name, dict(e.stats))
                          for e in line.events if e.name.startswith("chip.")]
    return [(n, s) for _, n, s in sorted(found, key=lambda x: x[0])]


def test_run_emits_host_spans_and_compiles_once(tmp_path):
    sim = ChipSim(compile_graph(synfire_graph(8)))
    first = _host_events(lambda: sim.run(10, exec_mode="event"),
                         tmp_path / "a")
    assert [n for n, _ in first] == ["chip.run", "chip.build",
                                     "chip.compile", "chip.dispatch"]
    args = first[0][1]
    assert args["n_ticks"] == 10 and not args["cached"]
    assert args["exec_mode"] == "event" and args["noc_mode"] == "dense"
    assert first[1][1]["w_store"] == "bitmask"       # the synaptic tables
    again = _host_events(lambda: sim.run(10, exec_mode="event"),
                         tmp_path / "b")
    assert [n for n, _ in again] == ["chip.run", "chip.dispatch"]
    assert again[0][1]["cached"]


def test_probed_run_compiles_once(tmp_path):
    sim = ChipSim(compile_graph(synfire_graph(8)))
    probes = ("link_flits", "dvfs")
    first = sim.run(16, probes=probes)
    events = _host_events(lambda: sim.run(16, probes=probes), tmp_path)
    assert [n for n, _ in events] == ["chip.run", "chip.dispatch"]
    again = sim.run(16, probes=probes)
    assert set(again) == set(first) == SYNFIRE_KEYS | {"probes"}
    # another probe set, or dropping the records, is another program
    events = _host_events(
        lambda: sim.run(16, probes=probes, keep_records=False),
        tmp_path / "b")
    assert [n for n, _ in events] == ["chip.run", "chip.compile",
                                      "chip.dispatch"]


FARM = {"background", "neuron", "synapse", "route"}


@pytest.fixture(scope="module")
def farm_board_sim():
    from repro.board import BoardSpec, compile_for_board
    from repro.chip.mesh_noc import MeshSpec
    from repro.chip.workloads import hybrid_farm_board_graph
    board = BoardSpec(2, 2, chip=MeshSpec(2, 1))
    return ChipSim(compile_for_board(hybrid_farm_board_graph(
        board, n_neurons=16, hidden=8)))


def test_farm_scan_holds_its_stages_and_the_xchip_tier(farm_board_sim):
    farm_board_sim.run(8)
    assert _stages_of_last_scan() == ENGINE | FARM | {"xchip"}
    paths = set(scopes.table()["jit_scan"].values())
    assert {f"chip_tick/semantics/{s}" for s in FARM} | {
        "chip_tick/noc", "chip_tick/noc/xchip"} <= paths


def test_farm_build_args_reach_the_build_span(farm_board_sim, tmp_path):
    sim = ChipSim(farm_board_sim.program)
    events = _host_events(lambda: sim.run(6), tmp_path)
    assert [n for n, _ in events] == ["chip.run", "chip.build",
                                      "chip.compile", "chip.dispatch"]
    args = events[1][1]
    assert args["n_pairs"] == 16
    assert (args["n_neurons"], args["hidden"]) == (16, 8)
    assert args["board"] == "2x2 chips of 2x1 QPEs"
    assert (args["links_onchip"], args["links_xchip"]) == (4 * 2, 8)
