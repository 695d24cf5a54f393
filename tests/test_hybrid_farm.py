"""The hybrid NEF -> event-MAC farm on a board, through the normal path
(``hybrid_farm_board_graph`` -> ``compile_for_board`` -> ``ChipSim``
with its defaults), against the benchmark's plain reference
(``bench/refs/hybrid_farm.py``): at a small size the integer records
match exactly and the float ones within the benchmark configuration's
limit; the 4x12 board compiles from the graph alone."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.board import BoardSpec, compile_board, compile_for_board
from repro.chip.chip import ChipSim
from repro.chip.workloads import (check_farm_placement,
                                  hybrid_farm_board_graph)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.refs import hybrid_farm  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/hybrid-farm-4x12.json")
                    .read_text())
DRIVE = {"amplitude": 0.8, "period_ticks": 97, "phase_step_ticks": 17,
         "table_ticks": 97}


def small_config() -> dict:
    """The cell's configuration on a 2x2 board of 2x1-QPE chips (32
    PEs, 16 channels) at 64 neurons and 16 hidden units."""
    cfg = json.loads(json.dumps(CONFIG))
    cfg["args"].update(board="2x2", chip="2x1", n_neurons=64, hidden=16)
    cfg["sizes"].update(chips_x=2, chips_y=2, chip_width=2, chip_height=1,
                        pes_per_chip=8, n_pairs=16, n_neurons=64, hidden=16)
    return cfg


@pytest.fixture(scope="module")
def small_farm():
    cfg = small_config()
    graph = hybrid_farm_board_graph(**cfg["args"], seed=7, **DRIVE)
    recs = ChipSim(compile_for_board(graph)).run(40)
    ref, _ = hybrid_farm.records(cfg, {"drive": DRIVE}, 7, 0, 40)
    return {k: np.asarray(v) for k, v in recs.items()}, ref


def test_small_farm_matches_the_plain_reference(small_farm):
    got, ref = small_farm
    checks = hybrid_farm.compare(ref, got)
    assert checks["int_mismatch"] == 0
    assert checks["float_rel_gap"] <= CONFIG["limits"]["float_rel_gap"]
    # the comparison sees real traffic: spikes, graded packets over
    # both NoC tiers, MLP output
    assert got["n_spk"].sum() > 0 and got["flits_xchip"].sum() > 0
    assert (got["link_flits"] > got["link_load"]).any()
    assert np.abs(got["hidden_out"]).max() > 0


@pytest.mark.parametrize("key", ["pl", "link_flits", "e_noc_xchip",
                                 "hidden_out"])
def test_small_farm_comparison_sees_each_record_kind(small_farm, key):
    """A changed entry of an integer record, a float energy or the MLP
    output fails the comparison."""
    got, ref = small_farm
    bad = dict(got)
    v = np.array(got[key], np.float64)
    idx = np.unravel_index(np.argmax(np.abs(v)), v.shape)
    v[idx] = v[idx] * 2 + 1
    bad[key] = v
    checks = hybrid_farm.compare(ref, bad)
    assert (checks["int_mismatch"] > 0
            or checks["float_rel_gap"] > CONFIG["limits"]["float_rel_gap"])


def _shuffled(n_pes: int, k: int) -> tuple:
    pes = np.random.default_rng(5).permutation(n_pes)
    return pes[:k], pes[k:2 * k]


# channel k's NEF and MLP PE ids for K = 5 channels on P PEs
PLACEMENTS = {
    "graph_order": (np.arange(5), 5 + np.arange(5), 10),
    "tail": (np.arange(5), 5 + np.arange(5), 13),
    "mlp_first": (5 + np.arange(5), np.arange(5), 10),
    "gap": (2 + np.arange(5), 9 + np.arange(5), 16),
    "shuffled": (*_shuffled(12, 5), 12),
}


@pytest.mark.parametrize("layout", sorted(PLACEMENTS))
def test_farm_placement_check(layout):
    """The farm's per-PE rows concatenate its NEF and MLP values, so it
    takes only the layout every compile gives it: NEF PEs 0..K-1, MLP
    PEs K..2K-1 and no PE after them."""
    nef_ids, mlp_ids, n_pes = PLACEMENTS[layout]
    if layout == "graph_order":
        check_farm_placement(nef_ids, mlp_ids, n_pes)
    else:
        with pytest.raises(ValueError, match="expected NEF PEs 0..4"):
            check_farm_placement(nef_ids, mlp_ids, n_pes)


@pytest.mark.parametrize("order", ["interleaved", "mlp_first"])
def test_farm_tick_rejects_a_reordered_graph(order):
    """A farm graph whose populations were reordered by hand compiles,
    but its tick refuses the placement instead of misplacing rows."""
    from dataclasses import replace
    from repro.chip.compile import compile as compile_graph
    from repro.chip.workloads import hybrid_farm_graph
    graph = hybrid_farm_graph(n_pairs=3, n_neurons=8, hidden=4)
    nef, mlp = graph.populations[:3], graph.populations[3:]
    pops = ([p for pair in zip(nef, mlp) for p in pair]
            if order == "interleaved" else mlp + nef)
    program = compile_graph(replace(graph, populations=pops))
    ChipSim(compile_graph(graph)).make_stepper()
    with pytest.raises(ValueError, match="expected NEF PEs 0..2"):
        ChipSim(program).make_stepper()


def test_board_entry_compiles_the_4x12_board_from_the_graph_alone():
    a = CONFIG["args"]
    graph = hybrid_farm_board_graph(**a, seed=3, **DRIVE)
    prog = compile_for_board(graph)
    board = BoardSpec.parse(a["board"], chip=a["chip"])
    assert graph.board == prog.board == board
    assert prog.n_pes == board.n_pes == 1536
    assert prog.noc.n_onchip_links == 48 * 20
    assert prog.noc.n_xchip_links == 160
    sem = graph.semantics
    assert (sem.n_pairs, sem.ens.n_neurons, sem.w_eff.shape[1]) == (768, 512,
                                                                    64)
    # the snake fill: every chip full, each channel's route as the
    # reference derives it, link by link
    assert all(u == 32 for u in prog.part.slots_used)
    routes = hybrid_farm.board_routes(CONFIG["sizes"])
    assert routes["n_links"] == prog.noc.n_links
    for k in (0, 31, 32, 400, 767):
        row = prog.sinc.link_ids[prog.sinc.source_ptr[k]:
                                 prog.sinc.source_ptr[k + 1]]
        assert sorted(row) == sorted(routes["link"][routes["src"] == k])
    np.testing.assert_array_equal(prog.tree_links_x[:768], routes["n_x"])
    # a full board leaves the min-cut refinement no slot to move into
    refined = compile_board(graph, board)
    assert refined.part.chip_of == prog.part.chip_of


def test_board_entry_needs_a_board():
    from repro.chip.workloads import hybrid_farm_graph
    with pytest.raises(ValueError, match="sized for no board"):
        compile_for_board(hybrid_farm_graph(n_pairs=2))
