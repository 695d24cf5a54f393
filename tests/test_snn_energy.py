"""Synfire chain + DVFS energy model: reproduces the paper's Table III and
Fig. 17/18 behavior."""
import numpy as np
import pytest

from repro.configs import paper
from repro.core.dvfs import DVFSController
from repro.core.energy import PEEnergyModel
from repro.core.snn import (build_synfire, gauss_noise_fx, simulate_synfire,
                            synfire_power_table)
pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st


@pytest.fixture(scope="module")
def sim():
    net = build_synfire(0)
    recs = simulate_synfire(net, 1200)
    return net, recs


def test_wave_propagates_around_ring(sim):
    _, recs = sim
    spk = np.asarray(recs["spikes_exc"]).sum(axis=2)      # (T, P)
    for p in range(8):
        strong = np.where(spk[:, p] > 100)[0]
        assert len(strong) >= 5, f"PE{p} did not sustain the synfire wave"
        # wave period = 8 PEs x 10 ms delay = 80 ms
        gaps = np.diff(strong[:5])
        assert np.all(np.abs(gaps - 80) <= 2), (p, gaps)


def test_pl_mostly_1_with_bursts(sim):
    """Fig. 18: sparse activity -> PL1 dominates; waves trigger PL3."""
    _, recs = sim
    pl = np.asarray(recs["pl"])
    frac = np.bincount(pl.ravel(), minlength=3) / pl.size
    assert frac[0] > 0.9
    assert frac[2] > 0.005                                 # waves reach PL3


def test_table_iii_reductions(sim):
    """Paper: total -60.4 %, baseline -63.4 %, neuron -21.2 %, syn -18.7 %."""
    _, recs = sim
    tab = synfire_power_table(recs)
    assert 0.55 <= tab["reduction"]["baseline"] <= 0.72
    assert 0.15 <= tab["reduction"]["neuron"] <= 0.27
    assert 0.04 <= tab["reduction"]["synapse"] <= 0.25
    assert 0.52 <= tab["reduction"]["total"] <= 0.72
    # absolute anchors from Table I: only-PL3 baseline == P_BL,3
    assert abs(tab["pl3"]["baseline"] - 66.44) < 0.1
    assert abs(tab["dvfs"]["baseline"] - 24.3) < 3.0       # paper: 24.3 mW


def test_gauss_noise_is_integer_exact_and_normal_shaped():
    """The background current is integer arithmetic plus one rounded
    float32 multiply — NumPy recomputes it bit for bit from the same
    random bits — with the requested standard deviation."""
    import jax
    key, t, sigma = jax.random.PRNGKey(3), 7, 9830
    noise = np.asarray(gauss_noise_fx(key, t, (500, 500), sigma))
    bits = np.asarray(jax.random.bits(jax.random.fold_in(key, t),
                                      (2, 500, 500), np.uint32))
    s = ((bits & 0xFFFF) + (bits >> 16)).sum(axis=0).astype(np.int64)
    scale = np.float32(sigma / np.sqrt((2.0 ** 32 - 1) / 3))
    expect = np.round((s - 2 * 0xFFFF).astype(np.float32) * scale)
    assert noise.dtype == np.int32
    np.testing.assert_array_equal(noise, expect.astype(np.int32))
    assert abs(noise.mean()) < 0.01 * sigma
    assert abs(noise.std() / sigma - 1) < 0.01
    assert np.abs(noise).max() <= np.sqrt(12) * sigma + 1


def test_energy_model_matches_hand_calc():
    em = PEEnergyModel()
    out = em.tick_energy(np.int32(0), 250, 1000, dvfs=True)
    tsp = (em.cycles_overhead + 250 * em.cycles_per_neuron
           + 1000 * em.cycles_per_syn) / 100e6
    expect = paper.PL1.p_baseline_w * tsp \
        + paper.PL1.p_baseline_w * (1e-3 - tsp) \
        + 250 * paper.PL1.e_neuron_j + 1000 * paper.PL1.e_synapse_j
    np.testing.assert_allclose(
        float(out["baseline"] + out["neuron"] + out["synapse"]), expect,
        rtol=1e-6)


@given(n=st.integers(0, 500))
def test_dvfs_controller_thresholds(n):
    c = DVFSController()
    pl = int(c.select_pl(n))
    if n < paper.SYNFIRE.l_th1:
        assert pl == 0
    elif n < paper.SYNFIRE.l_th2:
        assert pl == 1
    else:
        assert pl == 2


@given(a=st.integers(0, 300), b=st.integers(0, 300))
def test_dvfs_monotone(a, b):
    c = DVFSController()
    if a <= b:
        assert int(c.select_pl(a)) <= int(c.select_pl(b))
