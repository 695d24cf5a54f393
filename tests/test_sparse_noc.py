"""Sparse NoC path: CSR/col-plan accounting == dense einsum, exactly.

The engine auto-selects sparse vs dense by incidence density, so the two
representations must agree BITWISE — property-tested over random
``NetGraph``s, plus the golden 8-PE synfire program through the forced
sparse path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.graph import GRADED, SPIKE, NetGraph, Population, Projection
from repro.chip.workloads import hybrid_farm_graph, synfire_graph
from repro.core.snn import build_synfire, simulate_synfire

def random_graph(rng) -> NetGraph:
    """Random placeable NetGraph: 1-5 populations, 1-4 tiles each, random
    spike/graded projections (one packet class per source population).
    Shared with the hypothesis suite (test_sparse_noc_property)."""
    n_pops = int(rng.integers(1, 6))
    pops = [Population(name=f"p{i}", n=8, sram_bytes=64,
                       n_tiles=int(rng.integers(1, 5)),
                       align_qpe=bool(rng.integers(2)))
            for i in range(n_pops)]
    projs = []
    for i in range(n_pops):
        dsts = [j for j in range(n_pops) if rng.integers(2)]
        if not dsts:
            continue
        graded = bool(rng.integers(2))
        bits = int(rng.integers(1, 4097)) if graded else 0
        projs.extend(Projection(src=f"p{i}", dst=f"p{j}",
                                payload=GRADED if graded else SPIKE,
                                bits_per_packet=bits)
                     for j in dsts)
    return NetGraph(pops, projs, semantics=object(), name="rand")


def assert_sparse_equals_dense(graph, seed=0):
    """Sparse column-plan loads + energy == dense einsum, bitwise."""
    prog = compile_graph(graph)
    noc = prog.noc
    sinc = prog.sinc
    rng = np.random.default_rng(seed)
    packets = jnp.asarray(
        rng.integers(0, 200, prog.n_pes).astype(np.float32))
    pb = jnp.asarray(prog.payload_bits)

    dense_ll = np.asarray(noc.link_loads(packets, prog.inc))
    dense_fl = np.asarray(noc.flit_loads(packets, prog.inc, pb))

    cols, inv = sinc.device_col_plan()
    sp_ll = np.asarray(noc.link_loads_sparse(packets, cols, inv))
    sp_fl = np.asarray(noc.flit_loads_sparse(packets, cols, inv, pb))
    np.testing.assert_array_equal(sp_ll, dense_ll)
    np.testing.assert_array_equal(sp_fl, dense_fl)
    both_ll, both_fl = noc.noc_loads_sparse(packets, cols, inv, pb)
    np.testing.assert_array_equal(np.asarray(both_ll), dense_ll)
    np.testing.assert_array_equal(np.asarray(both_fl), dense_fl)

    # energy is representation-independent: tree_links == inc.sum(axis=1)
    np.testing.assert_array_equal(sinc.tree_links, prog.inc.sum(axis=1))
    e_sp = noc.traffic_energy_j(packets, jnp.asarray(sinc.tree_links,
                                                     jnp.float32), pb)
    e_de = noc.traffic_energy_j(packets, prog.inc.sum(axis=1), pb)
    np.testing.assert_array_equal(np.asarray(e_sp), np.asarray(e_de))


def assert_incidence_matches_route_walk(graph):
    """The arithmetic tree builder == the per-destination xy_route walk
    (the seed's reference implementation) for every compiled source."""
    prog = compile_graph(graph)
    noc = prog.noc
    for i in range(prog.n_pes):
        dsts = [tuple(prog.coords[j])
                for j in np.flatnonzero(prog.table.masks[i])]
        ref = {noc.link_index[lk]
               for lk in noc.tree_links(tuple(prog.coords[i]), dsts)}
        a, b = prog.sinc.source_ptr[i], prog.sinc.source_ptr[i + 1]
        got = set(prog.sinc.link_ids[a:b].tolist())
        assert got == ref, i
        # hop depth from the same pass
        assert prog.sinc.tree_hops[i] == noc.tree_hops(
            tuple(prog.coords[i]), dsts)


def test_sparse_equals_dense_fixed_seeds():
    for seed in range(12):
        rng = np.random.default_rng(seed)
        graph = random_graph(rng)
        assert_sparse_equals_dense(graph, seed)
        assert_incidence_matches_route_walk(graph)


def test_engine_sparse_dense_records_identical():
    """Same program, both engine paths, every NoC record bit-identical
    (dynamic graded payloads included via the farm workload)."""
    for graph in (synfire_graph(12),
                  hybrid_farm_graph(n_pairs=6, n_neurons=16, hidden=8,
                                    table_ticks=64)):
        sim = ChipSim(compile_graph(graph))
        a = sim.run(60, noc_mode="sparse")
        b = sim.run(60, noc_mode="dense")
        for k in ("link_load", "link_flits", "e_noc", "packets"):
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_golden_synfire_bit_identical_through_sparse_path():
    """The 8-PE test-chip benchmark stays bit-identical to the seed
    single-chip simulation when forced through the sparse NoC path."""
    sim = ChipSim(compile_graph(synfire_graph(8, seed=0)))
    recs = sim.run(300, noc_mode="sparse")
    ref = simulate_synfire(build_synfire(0), 300)
    for k in ("spikes_exc", "spikes_inh", "pl", "n_fifo", "syn_events",
              "packets"):
        assert np.array_equal(np.asarray(recs[k]), np.asarray(ref[k])), k
    # and the sparse NoC accounting equals the dense accounting
    dense = sim.run(300, noc_mode="dense")
    for k in ("link_load", "link_flits", "e_noc"):
        assert np.array_equal(np.asarray(recs[k]), np.asarray(dense[k])), k


def test_auto_mode_picks_sparse_for_sparse_trees():
    # board scale (224 links, density ~0.009): sparse
    sim = ChipSim(compile_graph(
        hybrid_farm_graph(n_pairs=128, n_neurons=8, hidden=4,
                          table_ticks=16)))
    assert sim.program.sinc.density < 0.25
    assert sim.use_sparse_noc() is True
    assert sim.use_sparse_noc("dense") is False
    # small chip (48 links): the dense GEMV is cheaper than the plan's
    # fixed op overhead, so auto stays dense
    small = ChipSim(compile_graph(synfire_graph(64)))
    assert small.program.sinc.n_links < 128
    assert small.use_sparse_noc() is False
    assert small.use_sparse_noc("sparse") is True
    with pytest.raises(ValueError, match="noc_mode"):
        sim.use_sparse_noc("bogus")


def test_auto_mode_falls_back_to_dense_for_heavy_fan_in():
    """An all-to-one graph is sparse by density but its sink-adjacent
    links are shared by ~P sources — the column plan would unroll O(P)
    ops per tick, so auto must pick the dense einsum (forced sparse stays
    available and bitwise-correct)."""
    n_srcs = 200
    pops = ([Population(name=f"s{i}", n=1, sram_bytes=16)
             for i in range(n_srcs)]
            + [Population(name="sink", n=1, sram_bytes=16)])
    projs = [Projection(src=f"s{i}", dst="sink") for i in range(n_srcs)]
    graph = NetGraph(pops, projs, semantics=object(), name="fan_in")
    prog = compile_graph(graph)
    sim = ChipSim(prog)
    assert prog.sinc.density < 0.25                 # passes the density gate
    assert prog.sinc.max_fan_in > 128               # but not the fan-in gate
    assert prog.sinc.max_fan_in == len(prog.sinc.col_plan[0])
    assert sim.use_sparse_noc() is False
    assert_sparse_equals_dense(graph)               # forced sparse still exact


def test_dense_inc_materializes_lazily():
    prog = compile_graph(synfire_graph(16))
    assert "inc" not in prog.__dict__            # not built yet
    inc = prog.inc
    assert inc.shape == (prog.n_pes, prog.noc.n_links)
    np.testing.assert_array_equal(inc, prog.sinc.dense())
    assert "inc" in prog.__dict__                # cached after first use


def test_hybrid_farm_runs_and_conserves_payload():
    """The board-scale hybrid farm honours the record contract: graded
    payload bits emitted == consumed one transport tick later."""
    g = hybrid_farm_graph(n_pairs=8, n_neurons=16, hidden=8, table_ticks=64)
    sim = ChipSim(compile_graph(g))
    recs = jax.block_until_ready(sim.run(60))
    out = np.asarray(recs["graded_bits_out"]).sum(axis=1)
    inn = np.asarray(recs["graded_bits_in"]).sum(axis=1)
    assert out.sum() > 0
    np.testing.assert_array_equal(out[:-1], inn[1:])
    assert inn[0] == 0
    # NEF populations precede MLP populations on the snake, so every
    # channel crosses >= 1 real mesh link
    assert sim.program.sinc.tree_links[:g.semantics.n_pairs].min() >= 1
    assert np.asarray(recs["e_noc"]).sum() > 0
