"""Synaptic storage: every synapse of a synfire projection holds one
weight, so ``build_synfire`` stores the connectivity as bit-packed masks
and ``synaptic_current`` counts arrivals with AND and popcount.  The
reference here is the int32 einsum of the arrivals with the weight
slabs the masks stand for: every record must be bitwise the same."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import SynfireSemantics, synfire_graph
from repro.core import snn
from repro.core.dvfs import DVFSController
from repro.core.energy import PEEnergyModel
from repro.core.snn import (build_synfire, make_synfire_tick,
                            synaptic_current, synfire_init_state)

TICKS = 40


def _fx(w):
    return int(np.round(np.float32(w) * 32768))


def _unpack(mask, n_src):
    """(words, P, n_tgt) uint32 masks -> (P, n_src, n_tgt) bools, bit b of
    word w being source 32w + b."""
    m = np.asarray(mask)
    bits = (m[:, :, None, :] >> np.arange(32, dtype=np.uint32)[:, None]) & 1
    bits = bits.transpose(1, 0, 2, 3).reshape(m.shape[1], -1, m.shape[2])
    assert not bits[:, n_src:].any()         # no bit past the last source
    return bits[:, :n_src].astype(bool)


def _bits(words, n):
    """(k, words) uint32 arrivals -> (k, n) 0/1 int32, bit b of word w
    being neuron 32w + b."""
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :n].astype(jnp.int32)


def _einsum_current(net, we, wi, rows=None):
    """``synaptic_current`` computed the plain way: the int32 s16.15
    weight slabs the masks stand for, times the unpacked arrivals."""
    sp = net.params
    w_ff = jnp.asarray(np.where(_unpack(net.m_ff, sp.n_exc),
                                np.int32(net.w_exc_fx), np.int32(0)))
    w_inh = jnp.asarray(np.where(_unpack(net.m_inh, sp.n_inh),
                                 np.int32(net.w_inh_fx), np.int32(0)))
    if rows is not None:
        w_ff, w_inh = w_ff[rows], w_inh[rows]
    i_ff = jnp.einsum("pe,pen->pn", _bits(we, sp.n_exc), w_ff,
                      preferred_element_type=jnp.int32)
    i_in = jnp.einsum("pi,pie->pe", _bits(wi, sp.n_inh), w_inh,
                      preferred_element_type=jnp.int32)
    return i_ff.at[:, :sp.n_exc].add(i_in)


@pytest.mark.parametrize("kw", [
    {},                          # the paper's 0.075 / -0.30
    {"w_exc": 1.0},              # 32768: one past int16
    {"w_inh": -1.5},             # -49152
])
def test_build_synfire_stores_masks(kw):
    net = build_synfire(0, n_pes=3, **kw)
    sp = net.params
    P, NE, NI, N = sp.n_pes, sp.n_exc, sp.n_inh, sp.neurons_per_core
    assert SynfireSemantics(net).build_args(program=None) == {
        "w_store": "bitmask", "synapse_bytes": net.synapse_bytes}
    assert (net.m_ff.shape, net.m_inh.shape) == ((7, P, N), (2, P, NE))
    assert net.m_ff.dtype == net.m_inh.dtype == jnp.uint32
    assert net.synapse_bytes == 4 * (7 * P * N + 2 * P * NE)
    assert (net.w_exc_fx, net.w_inh_fx) == (
        _fx(kw.get("w_exc", 0.075)), _fx(kw.get("w_inh", -0.30)))
    ff, inh = _unpack(net.m_ff, NE), _unpack(net.m_inh, NI)
    # every target draws its fan-in; every source keeps its out-degree
    assert (ff.sum(axis=1) == sp.fan_in_exc).all()
    assert (inh.sum(axis=1) == sp.fan_in_inh).all()
    assert np.array_equal(ff.sum(axis=2), np.asarray(net.deg_ff))
    assert np.array_equal(inh.sum(axis=2), np.asarray(net.deg_inh))


@pytest.mark.parametrize("rows", [None, "gathered"])
@pytest.mark.parametrize("kw", [{}, {"w_exc": 1.0}, {"w_inh": -1.5}])
def test_synaptic_current_is_the_int32_einsum(kw, rows):
    """AND and popcount over the masks give the einsum's int32 sums, for
    all PEs and for a gather of PEs (repeats included), on arrival words
    with every bit set at random, the bits past the last neuron too."""
    net = build_synfire(1, n_pes=5, **kw)
    k = 5 if rows is None else 7
    words = jax.random.bits(jax.random.PRNGKey(3), (k, 9), jnp.uint32)
    we, wi = words[:, :7], words[:, 7:]
    if rows is not None:
        rows = jnp.asarray([4, 0, 0, 2, 3, 1, 4])
    got = synaptic_current(net, we, wi, rows=rows)
    assert got.dtype == jnp.int32 and got.shape == (k, 250)
    assert np.array_equal(got, _einsum_current(net, we, wi, rows=rows))


@pytest.fixture(scope="module")
def ring64():
    return synfire_graph(64)


@pytest.fixture(scope="module")
def shot64():
    """A 64-PE ring under shot noise."""
    return synfire_graph(64, noise_model="shot", noise_sigma=0.0,
                         kicks_per_tick=4, kick=0.5)


def _fallback_run(net):
    """The event tick with a 4-PE input buffer, so that most ticks
    overflow it and take the dense fallback branch."""
    sp = net.params
    tick = make_synfire_tick(net, dvfs=DVFSController(sp.l_th1, sp.l_th2),
                             em=PEEnergyModel(), key=jax.random.PRNGKey(1),
                             event=True, src_cap=4)
    return jax.lax.scan(tick, synfire_init_state(net),
                        jnp.arange(TICKS))[1]


def _same_records(graph, mode, monkeypatch):
    """Run ``graph`` on the path ``mode`` with the masks, then again with
    the synaptic current computed by the int32 einsum, and check that
    every record, spikes, packets, ``syn_events`` and energies included,
    is bitwise the same; returns the records."""
    def run():
        if mode == "fallback":
            return _fallback_run(graph.semantics.net)
        # a fresh program and simulator, so nothing compiled is reused
        fresh = dataclasses.replace(
            graph, semantics=SynfireSemantics(graph.semantics.net))
        return ChipSim(compile_graph(fresh)).run(TICKS, exec_mode=mode)
    got = jax.tree.map(np.asarray, run())
    with monkeypatch.context() as m:
        m.setattr(snn, "synaptic_current", _einsum_current)
        ref = jax.tree.map(np.asarray, run())
    assert set(got) == set(ref)
    for k in got:
        assert np.array_equal(got[k], ref[k]), k
    if mode == "fallback":
        assert (got["n_fifo"] > 0).sum(axis=1).max() > 4
    return got


@pytest.mark.parametrize("mode", ["dense", "event", "auto", "fallback",
                                  "shot"])
def test_bitmasks_give_the_int32_records(mode, ring64, shot64, monkeypatch):
    """Masks counted with AND and popcount give the records of the int32
    einsum over the same synapses, on every path of the tick."""
    if mode == "shot":
        recs = _same_records(shot64, "event", monkeypatch)
        assert recs["syn_events"].sum() > 0
    else:
        recs = _same_records(ring64, mode, monkeypatch)
        assert recs["spikes_exc"].sum(axis=(0, 2))[1:4].min() > 0
