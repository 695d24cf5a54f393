"""Synaptic slab storage: ``build_synfire`` keeps the s16.15 weight slabs
in int16 when every weight fits, in int32 otherwise, and the width never
changes a record (the einsums accumulate in int32)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import SynfireSemantics, synfire_graph
from repro.core.dvfs import DVFSController
from repro.core.energy import PEEnergyModel
from repro.core.snn import (build_synfire, make_synfire_tick,
                            synfire_init_state)

TICKS = 40


@pytest.mark.parametrize("kw, dtype", [
    ({}, jnp.int16),                        # the paper's 0.075 / -0.30
    ({"w_exc": 32767 / 32768}, jnp.int16),  # the largest weight int16 holds
    ({"w_exc": 1.0}, jnp.int32),            # 32768: one past int16
    ({"w_inh": -1.5}, jnp.int32),           # -49152
])
def test_slab_width_follows_the_weights(kw, dtype):
    net = build_synfire(0, n_pes=2, **kw)
    assert net.w_ff.dtype == dtype and net.w_inh.dtype == dtype
    # the width ``ChipSim`` shows on its ``chip.build`` span
    assert SynfireSemantics(net).build_args(program=None) == {
        "w_dtype": jnp.dtype(dtype).name}
    w_exc, w_inh = kw.get("w_exc", 0.075), kw.get("w_inh", -0.30)
    assert set(np.unique(np.asarray(net.w_ff))) == {
        0, int(np.round(np.float32(w_exc) * 32768))}
    assert set(np.unique(np.asarray(net.w_inh))) == {
        0, int(np.round(np.float32(w_inh) * 32768))}


def _widened(net):
    return dataclasses.replace(net, w_ff=net.w_ff.astype(jnp.int32),
                               w_inh=net.w_inh.astype(jnp.int32))


@pytest.fixture(scope="module")
def ring64():
    """A 64-PE ring with int16 slabs, and its graph with the same slabs
    widened to int32."""
    graph = synfire_graph(64)
    net = graph.semantics.net
    assert net.w_ff.dtype == jnp.int16
    wide = dataclasses.replace(graph,
                               semantics=SynfireSemantics(_widened(net)))
    return graph, wide


def _fallback_run(net):
    """The event tick with a 4-PE input buffer, so that most ticks
    overflow it and take the dense fallback branch."""
    sp = net.params
    tick = make_synfire_tick(net, dvfs=DVFSController(sp.l_th1, sp.l_th2),
                             em=PEEnergyModel(), key=jax.random.PRNGKey(1),
                             event=True, src_cap=4)
    return jax.lax.scan(tick, synfire_init_state(net),
                        jnp.arange(TICKS))[1]


@pytest.mark.parametrize("mode", ["dense", "event", "auto", "fallback"])
def test_int16_slabs_give_the_int32_records(mode, ring64):
    """Every record of every execution path, spikes, packets,
    ``syn_events`` and energies included, is bitwise the same with int16
    and with int32 slabs."""
    def run(graph):
        if mode == "fallback":
            return _fallback_run(graph.semantics.net)
        return ChipSim(compile_graph(graph)).run(TICKS, exec_mode=mode)
    narrow, wide = (jax.tree.map(np.asarray, run(g)) for g in ring64)
    assert set(narrow) == set(wide)
    for k in narrow:
        assert np.array_equal(narrow[k], wide[k]), k
    spikes = narrow["spikes_exc"].sum(axis=(0, 2))
    assert spikes[1:4].min() > 0        # the wave crossed three slabs
    if mode == "fallback":
        assert (narrow["n_fifo"] > 0).sum(axis=1).max() > 4
