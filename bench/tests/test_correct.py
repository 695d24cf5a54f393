"""``correct`` comes out false when it should: the control (the plain
reference in bfloat16) fails each cell's comparison, and so does a run
with the timed path broken underneath, once per fault the cell can
have.  At test sizes on the CPU; the harness's look for a chip is
skipped, the rest of a run is driven as ``bench.run`` drives it."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import device, run, spec
from bench.kinds import engine

PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
         "hbm_bytes_per_s": 819e9}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(device, "peaks_for", lambda kind: PEAKS)


SHOT = {"noise_model": "shot", "kicks_per_tick": 4, "kick": 0.5}


def synfire_cell(drive=None):
    cell = spec.load_cell("synfire4096-gauss")
    if drive:
        cell.traffic["drive"] = drive
    cell.config["args"]["n_pes"] = cell.config["sizes"]["n_pes"] = 8
    cell.traffic["ticks_per_job"] = 300
    return cell


def drive(cell, seed=2 ** 31 + 17, seconds=1.5):
    kind = spec.load_kind(cell.config)
    res = kind.run(cell, seed, seconds, False, time.perf_counter(),
                   jax.devices()[:1])
    return run.result_line(cell, res, False)


# ------------------------------------------------------------ synfire ring

@pytest.mark.parametrize("background", [None, SHOT], ids=["gauss", "shot"])
def test_synfire_sound_run_is_correct(background):
    line = drive(synfire_cell(background))
    assert line["correct"], line["checks"]
    assert line["checks"]["int_mismatch"]["value"] == 0


def test_synfire_control_fails():
    c = engine.control(synfire_cell(), 2 ** 31 + 17, 1.0)
    assert c["int_mismatch"] > 0


def _patch_run(monkeypatch, broken):
    from repro.chip.chip import ChipSim
    orig = ChipSim.run
    monkeypatch.setattr(ChipSim, "run",
                        lambda self, n, **kw: broken(self, orig, n, **kw))


def test_synfire_altered_spike_fails(monkeypatch):
    def flip(self, orig, n, **kw):
        recs = dict(orig(self, n, **kw))
        recs["spikes_exc"] = recs["spikes_exc"].at[n // 2, 3, 7].set(
            1 - recs["spikes_exc"][n // 2, 3, 7])
        return recs
    _patch_run(monkeypatch, flip)
    line = drive(synfire_cell())
    assert not line["correct"]
    assert line["checks"]["int_mismatch"]["value"] == 1


def test_synfire_state_left_unchanged_fails(monkeypatch):
    def stale(self, orig, n, seed=1, **kw):
        init, step, params = self.make_stepper(seed)
        return jax.lax.scan(lambda s, t: (s, step(params, s, t)[1]), init,
                            jnp.arange(n))[1]
    _patch_run(monkeypatch, stale)
    line = drive(synfire_cell())
    assert not line["correct"]


def test_synfire_half_the_ring_left_out_fails(monkeypatch):
    def half(self, orig, n, **kw):
        recs = dict(orig(self, n, **kw))
        for k in ("spikes_exc", "spikes_inh"):
            v = recs[k]
            keep = (jnp.arange(v.shape[1]) % 2 == 0)[None, :, None]
            recs[k] = jnp.where(keep, v, 0).astype(v.dtype)   # odd PEs lost
        return recs
    _patch_run(monkeypatch, half)
    line = drive(synfire_cell())
    assert not line["correct"]
    assert line["checks"]["int_mismatch"]["value"] > 0
