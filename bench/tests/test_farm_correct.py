"""``correct`` of the hybrid farm cell: a sound run of the program
matches the plain reference (``bench/refs/hybrid_farm.py``), and the
comparison fails a run with half the channels' MLP output lost, a run
with one chip-to-chip flit moved to another link, and the control (the
reference with bfloat16 MLP weights).  At a test size on the CPU: a 2x2
board of 2x1-QPE chips, 64 neurons and 16 hidden units per channel, 40
ticks; the harness's look for a chip is skipped, the rest of a run is
driven as ``bench.run`` drives it."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import device, run, spec
from bench.kinds import engine
from bench.refs import hybrid_farm

PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
         "hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 17


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(device, "peaks_for", lambda kind: PEAKS)


def farm_cell():
    cell = spec.load_cell("farm4x12-sim")
    cfg = cell.config
    cfg["args"].update(board="2x2", chip="2x1", n_neurons=64, hidden=16)
    cfg["sizes"].update(chips_x=2, chips_y=2, chip_width=2, chip_height=1,
                        pes_per_chip=8, n_pairs=16, n_neurons=64, hidden=16)
    cell.traffic["ticks_per_job"] = 40
    return cell


def drive(cell, traced=False):
    res = spec.load_kind(cell.config).run(cell, SEED, 0.5, traced,
                                          time.perf_counter(),
                                          jax.devices()[:1])
    return run.result_line(cell, res, traced), res


def test_cell_runs_the_published_widths_on_the_whole_board():
    cell = spec.load_cell("farm4x12-sim")
    s, a = cell.config["sizes"], cell.config["args"]
    assert (a["n_neurons"], a["hidden"]) == (s["n_neurons"], s["hidden"]) \
        == (512, 64)
    assert 2 * s["n_pairs"] == s["chips_x"] * s["chips_y"] * \
        s["pes_per_chip"] == 1536
    routes = hybrid_farm.board_routes(s)
    assert routes["n_links"] == 48 * 20 + 160     # two tiers
    assert (routes["n_x"] > 0).mean() > 0.9       # most channels span chips


def test_farm_sound_run_is_correct():
    line, res = drive(farm_cell(), traced=False)
    assert line["correct"], line["checks"]
    assert line["checks"]["int_mismatch"]["value"] == 0
    assert line["checks"]["float_rel_gap"]["value"] < 1e-6


def test_farm_work_counts_what_arrives():
    cell = farm_cell()
    ref, net = hybrid_farm.records(cell.config, cell.traffic, 5, 0, 40)
    work = hybrid_farm.tick_work(ref, net)
    arrived = ref["n_spk"][:-1].sum() / 40
    assert work["spikes_arrived_per_tick"] == pytest.approx(arrived)
    assert work["ops_per_tick"] == pytest.approx(2 * 16 * arrived)
    assert work["bytes_per_tick"] > 16 * 64 * 2 * 4.25


def _patch_run(monkeypatch, broken):
    from repro.chip.chip import ChipSim
    orig = ChipSim.run
    monkeypatch.setattr(ChipSim, "run",
                        lambda self, n, **kw: broken(self, orig, n, **kw))


def test_farm_half_the_channels_output_lost_fails(monkeypatch):
    def half(self, orig, n, **kw):
        recs = dict(orig(self, n, **kw))
        h = recs["hidden_out"]
        keep = (jnp.arange(h.shape[1]) % 2 == 0)[None, :, None]
        recs["hidden_out"] = jnp.where(keep, h, 0.0)
        return recs
    _patch_run(monkeypatch, half)
    line, _ = drive(farm_cell())
    assert not line["correct"]
    assert line["checks"]["float_rel_gap"]["value"] > 0.1


def test_farm_moved_xchip_flit_fails(monkeypatch):
    """One flit taken off a loaded chip-to-chip link and put on another
    one: the tier totals are unchanged, the per-link records are not."""
    def move(self, orig, n, **kw):
        recs = dict(orig(self, n, **kw))
        x = np.flatnonzero(np.asarray(self.program.noc.xlink_mask))
        fl = np.asarray(recs["link_flits"])
        t = int(np.flatnonzero(fl[:, x].sum(axis=1) > 0)[0])
        a = x[np.argmax(fl[t, x] > 0)]
        b = x[x != a][0]
        recs["link_flits"] = recs["link_flits"].at[t, a].add(-1).at[
            t, b].add(1)
        return recs
    _patch_run(monkeypatch, move)
    line, _ = drive(farm_cell())
    assert not line["correct"]
    assert line["checks"]["int_mismatch"]["value"] == 2


def test_farm_control_fails_float_gap_only():
    cell = farm_cell()
    c = engine.control(cell, SEED, 1.0)
    assert c["int_mismatch"] == 0
    assert c["float_rel_gap"] > cell.config["limits"]["float_rel_gap"]
