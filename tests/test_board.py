"""Board-level multi-chip simulator: golden 1x1 anchor + hierarchical
routing + tiered accounting.

The load-bearing guarantee is the golden anchor: a 1x1-chip board runs
the SAME compile + engine path as today's single chip — identical CSR
incidence, identical per-tick records, bit for bit.  On real boards the
hierarchical router must cover every projection (checked by walking the
per-source link sets against ``BoardNoc.link_endpoints``) and the
per-tier accounting must split exactly.
"""
import numpy as np
import pytest

from repro.board import BoardSpec, compile_board, partition
from repro.board.route import chip_tree
from repro.chip.chip import ChipSim, chip_power_table
from repro.chip.compile import compile as compile_graph
from repro.chip.graph import NetGraph, Population, Projection
from repro.chip.mesh_noc import MeshSpec
from repro.chip.workloads import (board_workload, dnn_board_graph,
                                  hybrid_farm_board_graph, hybrid_graph,
                                  synfire_board_graph, synfire_graph)


# -------------------------------------------------------------------------
# Golden anchor: 1x1 board == single chip, bit for bit
# -------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: synfire_graph(8, seed=0),
    lambda: hybrid_graph(n_neurons=64, hidden=16, n_ticks=60),
])
def test_board_1x1_bitwise_identical_to_single_chip(make):
    graph = make()
    pa = compile_graph(graph)
    pb = compile_board(make(), BoardSpec(1, 1, chip=pa.mesh))
    # compile artifacts identical: placement, routing, CSR incidence
    np.testing.assert_array_equal(pa.coords, pb.coords)
    np.testing.assert_array_equal(pa.table.masks, pb.table.masks)
    np.testing.assert_array_equal(pa.payload_bits, pb.payload_bits)
    np.testing.assert_array_equal(pa.sinc.link_ids, pb.sinc.link_ids)
    np.testing.assert_array_equal(pa.sinc.source_ptr, pb.sinc.source_ptr)
    np.testing.assert_array_equal(pa.sinc.tree_hops, pb.sinc.tree_hops)
    assert pa.sinc.n_links == pb.sinc.n_links
    assert pb.noc.n_xchip_links == 0
    assert (pb.tree_links_x == 0).all()
    # run records identical — same keys (no tier records on one chip),
    # same bits, through the engine's auto-selected NoC path
    ra, rb = ChipSim(pa).run(90), ChipSim(pb).run(90)
    assert set(ra) == set(rb)
    for k in ra:
        assert np.array_equal(np.asarray(ra[k]), np.asarray(rb[k])), k


# -------------------------------------------------------------------------
# Hierarchical route correctness: walk every source's stitched tree
# -------------------------------------------------------------------------

def _route_coverage(prog):
    """For each source PE, follow its link set from its own (chip, coord)
    node and assert it reaches EVERY destination PE of the routing table
    (a projection that lost a destination would fail here)."""
    noc = prog.noc
    for p in range(prog.n_pes):
        a, b = prog.sinc.source_ptr[p], prog.sinc.source_ptr[p + 1]
        links = [noc.link_endpoints(int(l)) for l in prog.sinc.link_ids[a:b]]
        assert len({tuple(map(tuple, (u, v))) for u, v in links}) == \
            len(links), f"source {p}: duplicate link in tree"
        reach = {(int(prog.chip_of_pe[p]), tuple(prog.coords_local[p]))}
        frontier = True
        while frontier:
            frontier = False
            for (c0, xy0), (c1, xy1) in links:
                if (c0, tuple(xy0)) in reach and (c1, tuple(xy1)) not in reach:
                    reach.add((c1, tuple(xy1)))
                    frontier = True
        for q in np.flatnonzero(prog.table.masks[p]):
            node = (int(prog.chip_of_pe[q]), tuple(prog.coords_local[q]))
            assert node in reach, f"source {p} never reaches PE {q}"


def test_every_projection_routed_across_chips():
    board = BoardSpec(3, 2, chip=MeshSpec(2, 2))
    graph = synfire_board_graph(board)          # ring spans every chip
    prog = compile_board(graph, board)
    assert prog.n_pes == board.n_pes
    assert (prog.part.chips_of_graph() > 0).all()
    assert prog.tree_links_x.sum() > 0          # the ring crosses chips
    _route_coverage(prog)


def test_chip_tree_is_a_tree():
    board = BoardSpec(4, 3)
    tree = chip_tree(board, src_chip=5, dst_chips=[0, 3, 7, 11])
    entries = [e for e, _ in tree.values() if e is not None]
    assert len(entries) == len(tree) - 1        # one entry per non-source
    # edges = nodes - 1 (tree, not a DAG with rejoins)
    n_edges = sum(len(x) for _, x in tree.values())
    assert n_edges == len(tree) - 1


# -------------------------------------------------------------------------
# Tiered accounting: the split is exact and consistent
# -------------------------------------------------------------------------

@pytest.fixture(scope="module")
def farm_2x2():
    board = BoardSpec(2, 2, chip=MeshSpec(2, 2))
    graph = hybrid_farm_board_graph(board, n_neurons=16, hidden=8,
                                    table_ticks=64)
    rep = board_workload(graph, board, n_ticks=60)
    return board, rep


def test_board_tier_split_is_exact(farm_2x2):
    board, rep = farm_2x2
    recs, prog = rep["recs"], rep["program"]
    flits = np.asarray(recs["link_flits"])
    loads = np.asarray(recs["link_load"])
    xmask = np.asarray(prog.noc.xlink_mask) > 0
    # per-tick tier records == masked per-link sums, flit conservation
    # across the chip-boundary tier (nothing dropped, nothing invented)
    np.testing.assert_array_equal(np.asarray(recs["flits_xchip"]),
                                  flits[:, xmask].sum(axis=1))
    np.testing.assert_array_equal(np.asarray(recs["load_xchip"]),
                                  loads[:, xmask].sum(axis=1))
    assert rep["flits_xchip"] > 0               # channels do cross chips
    assert 0 < rep["xchip_frac"] < 1
    # energy split: tiers sum to the total (tiered pricing, two pj rates)
    e = np.asarray(recs["e_noc"], np.float64)
    e_x = np.asarray(recs["e_noc_xchip"], np.float64)
    assert (e_x <= e + 1e-30).all()
    np.testing.assert_allclose(
        e, e_x + _onchip_energy_j(prog, recs), rtol=1e-6, atol=1e-24)


def _onchip_energy_j(prog, recs):
    """Reference on-chip share: per-source packets x on-chip tree links
    x packet bits x the on-chip pJ/bit-hop."""
    import jax.numpy as jnp
    pk = np.asarray(recs["packets"], np.float64)
    pb = np.asarray(recs.get("payload_bits",
                             np.broadcast_to(prog.payload_bits, pk.shape)))
    pbits = np.asarray(prog.noc.packet_bits(jnp.asarray(pb)), np.float64)
    tl_on = (prog.sinc.tree_links - prog.tree_links_x).astype(np.float64)
    bits = (pk * tl_on * pbits).sum(axis=-1)
    return bits * prog.noc.spec.pj_per_bit_hop * 1e-12


# sha256 (first 16 hex digits) of each farm_2x2 record as the farm
# produced it while its drive was hard-coded (amplitude 0.8, period 97,
# phase step 17 ticks); ``hidden_out``, a float GEMM whose last bits
# follow the CPU's thread partitioning, by its sum
FARM_2X2_DIGESTS = {
    "active_frac": "224b9369a735067c", "active_sources": "eb095380f8d20316",
    "e_dvfs_baseline": "fe29135ab0eaf2a0", "e_dvfs_neuron": "eb7fe1995c8fb49e",
    "e_dvfs_synapse": "3a1d2407d62c4353", "e_noc": "e122a574dfe4655d",
    "e_noc_xchip": "21a685f6ad85aeff", "e_pl3_baseline": "85fb0c83b6917007",
    "e_pl3_neuron": "58f315e5e0cb7791", "e_pl3_synapse": "e63c4e6b09f2c203",
    "flits_xchip": "46e6bcb8bdbf45ac", "graded_bits_in": "2253632cf884e4c2",
    "graded_bits_out": "e5ad06e4e318339d", "link_flits": "05ed3b95f2ea4709",
    "link_load": "05ed3b95f2ea4709", "load_xchip": "46e6bcb8bdbf45ac",
    "n_fifo": "bc5c46004a0d93f9", "n_spk": "a734da04ca756804",
    "packets": "ccb8951138651ee5", "payload_bits": "e5ad06e4e318339d",
    "pl": "0299f757a85a1aad", "syn_events": "37c1d380790348f4",
    "touched_links": "9ff47629483e3f46",
    "touched_links_onchip": "211c754f32c344dd",
    "touched_links_xchip": "d5f2460cae7128a6"}
FARM_2X2_HIDDEN_SUM = 370.8804432605393


def test_farm_drive_defaults_keep_the_records(farm_2x2):
    """The farm's drive arguments at their defaults give the records of
    the hard-coded drive they replaced, bit for bit."""
    import hashlib
    recs = farm_2x2[1]["recs"]
    assert set(recs) == set(FARM_2X2_DIGESTS) | {"hidden_out"}
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in recs.items() if k != "hidden_out"}
    assert got == FARM_2X2_DIGESTS
    hidden = np.asarray(recs["hidden_out"], np.float64)
    assert hidden.shape == (60, 32, 8)
    assert hidden.sum() == pytest.approx(FARM_2X2_HIDDEN_SUM, rel=1e-9)
    # the defaults are the drive: spelled out, they give the same program
    explicit = hybrid_farm_board_graph(
        farm_2x2[0], n_neurons=16, hidden=8, table_ticks=64, amplitude=0.8,
        period_ticks=97, phase_step_ticks=17)
    again = ChipSim(compile_board(explicit, farm_2x2[0])).run(60)
    for k in recs:
        assert np.array_equal(np.asarray(again[k]), np.asarray(recs[k])), k


def test_power_table_reports_xchip_tier(farm_2x2):
    board, rep = farm_2x2
    tab = rep["table"]
    assert tab["board"] == (2, 2)
    x = tab["noc"]["xchip"]
    assert x["n_links"] == rep["program"].noc.n_xchip_links
    assert 0 < x["flits_frac"] < 1
    # chip-to-chip hops cost ~12x the energy per bit: crossing traffic
    # dominates NoC energy long before it dominates flit counts
    assert x["energy_frac"] > x["flits_frac"]


def test_board_sparse_dense_and_pallas_agree():
    board = BoardSpec(2, 2, chip=MeshSpec(2, 1))
    prog = compile_board(synfire_board_graph(board), board)
    sim = ChipSim(prog)
    a = sim.run(60, noc_mode="sparse")
    b = sim.run(60, noc_mode="dense")
    c = sim.run(60, noc_mode="sparse", link_load_impl="pallas")
    for k in ("link_load", "link_flits", "e_noc", "flits_xchip",
              "load_xchip", "e_noc_xchip"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert np.array_equal(np.asarray(a[k]), np.asarray(c[k])), k


# -------------------------------------------------------------------------
# Partitioner basics (the hypothesis suite drives the random cases)
# -------------------------------------------------------------------------

def test_partition_respects_capacity_and_errors_clearly():
    board = BoardSpec(2, 1, chip=MeshSpec(1, 1))     # 2 chips x 4 PEs
    graph = synfire_graph(8)
    part = partition(graph, board)
    assert sorted(part.chip_of.values()) == [0] * 4 + [1] * 4
    assert all(u <= board.chip.n_pes for u in part.slots_used)
    with pytest.raises(ValueError, match="does not fit the"):
        partition(synfire_graph(9), board)
    fat = NetGraph([Population("fat", 1, 64, n_tiles=5)], [],
                   semantics=object())
    with pytest.raises(ValueError, match="one 1x1 QPE chip holds"):
        partition(fat, board)


def test_kernel_knob_validated_even_on_dense_path():
    """A typo'd link_load_impl must error up front, even when the dense
    einsum wins the auto-selection and the sparse plan is never built."""
    sim = ChipSim(compile_graph(synfire_graph(8)))
    assert sim.use_sparse_noc() is False
    with pytest.raises(ValueError, match="link_load_impl"):
        sim.run(4, link_load_impl="bogus")


def test_compile_board_rejects_mismatched_partition():
    graph = synfire_graph(8)
    part = partition(graph, BoardSpec(2, 1, chip=MeshSpec(1, 1)))
    with pytest.raises(ValueError, match="partition was built for"):
        compile_board(graph, BoardSpec(2, 2, chip=MeshSpec(2, 2)),
                      part=part)


def test_partition_refinement_reduces_cut():
    """A pair graph laid out nef0..nefK mlp0..mlpK greedily splits pairs
    across chips; refinement must pull each pair back together (or at
    least never make the cut worse)."""
    board = BoardSpec(2, 2, chip=MeshSpec(2, 2))
    graph = hybrid_farm_board_graph(board, n_neurons=16, hidden=8)
    rough = partition(graph, board, refine=False)
    fine = partition(graph, board, refine=True)
    assert fine.cut_flits <= rough.cut_flits
    assert all(u <= board.chip.n_pes for u in fine.slots_used)


def test_dnn_board_pipeline_runs_across_chips():
    board = BoardSpec(2, 2, chip=MeshSpec(4, 2))
    graph = dnn_board_graph(board)
    rep = board_workload(graph, board, n_ticks=120)
    assert rep["n_chips_used"] > 1
    assert rep["flits_xchip"] > 0
    assert np.asarray(rep["recs"]["frame_out"]).sum() > 0
