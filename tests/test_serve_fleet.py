"""The neuromorphic serving tier (repro.serve.fleet + repro.serve.queue).

Anchors, strongest first:

* **fleet of 1 == plain engine, bitwise** — a single-session fleet's
  streamed per-tick outputs equal ``ChipSim.run`` of the same program
  with the whole stimulus preloaded: the vmapped batched body at w=1 is
  the unbatched engine, and segment-wise stimulus encoding equals
  whole-table encoding (per-row quantization);
* **preemption/restore is bitwise invisible** — sessions evicted when
  the fleet narrows (QueueDVFS) and resumed later — in the same engine
  or, via ``repro.ckpt``, in a different one — produce outputs identical
  to an uninterrupted run;
* width follows the queue's offered load through the batch levels;
* a streamed stimulus equal to a baked table drives each network's one
  tick to the same records, bitwise;
* both served scenarios (adaptive control, KWS hybrid farm) and the
  board-compiled program serve end-to-end under Poisson traffic.
"""
import numpy as np
import pytest

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.stimulus import StreamedStimulus, TableStimulus
from repro.chip.workloads import hybrid_farm_graph
from repro.core.dvfs import QueueDVFS
from repro.core.nef import build_ensemble
from repro.learn.adaptive import adaptive_control_graph
from repro.serve.fleet import (FleetEngine, PoissonTraffic, Session,
                               SessionTable, adaptive_scenario,
                               kws_scenario)
from repro.serve.queue import RequestQueue, percentiles, select_width

TC = 32


@pytest.fixture(scope="module")
def adaptive_sc():
    return adaptive_scenario(n_neurons=32)


def _solo(sc, seed, total_ticks):
    """Uninterrupted single-session reference run (width-1 fleet)."""
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(2,), batch_levels=(1, 1)),
                      capacity=1)
    s = Session(sid=0, stream=sc.stream(seed), total_ticks=total_ticks)
    return eng.serve(None, sessions=[s])["sessions"][0]


# ---------------------------------------------------------------- queue

def test_request_queue_fifo_front_and_stats():
    q = RequestQueue()
    q.extend(["a", "b", "c"])
    q.submit("p", front=True)            # preempted session jumps the line
    assert len(q) == 4 and q.depth == 4
    assert q.take(2) == ["p", "a"]
    assert q.take(10) == ["b", "c"]
    assert not q
    st = q.stats()
    assert st["submitted"] == 4 and st["taken"] == 4 and st["waiting"] == 0
    assert st["wait_p99_s"] >= st["wait_p50_s"] >= 0.0


def test_select_width_tracks_offered_load():
    dvfs = QueueDVFS(thresholds=(4, 16), batch_levels=(8, 32, 128))
    q = RequestQueue()
    assert select_width(dvfs, q, in_flight=0) == 8
    q.extend(range(5))                   # waiting alone crosses threshold
    assert select_width(dvfs, q, in_flight=0) == 32
    q.take(5)
    # in-flight work keeps the width up after the queue drains
    assert select_width(dvfs, q, in_flight=20) == 128
    assert select_width(dvfs, q, in_flight=20, capacity=16) == 16


def test_percentiles_empty_and_ordered():
    assert percentiles([]) == {"p50": 0.0, "p99": 0.0}
    p = percentiles(range(100))
    assert p["p50"] < p["p99"]


def test_percentiles_single_sample_and_none():
    """A single sample is its own p50 AND p99, and None entries
    (sessions that never completed) are dropped, not propagated."""
    assert percentiles([7.5]) == {"p50": 7.5, "p99": 7.5}
    assert percentiles([None, 3.0, None]) == {"p50": 3.0, "p99": 3.0}
    assert percentiles([None, None]) == {"p50": 0.0, "p99": 0.0}
    p = percentiles([None, 1.0, 2.0], ps=(0, 50, 100))
    assert (p["p0"], p["p50"], p["p100"]) == (1.0, 1.5, 2.0)


def test_request_queue_stats_before_any_traffic():
    st = RequestQueue().stats()
    assert st == {"submitted": 0, "taken": 0, "waiting": 0,
                  "wait_p50_s": 0.0, "wait_p99_s": 0.0}


def test_select_width_at_exact_ladder_boundaries():
    """The thresholds are inclusive lower edges: offered load EXACTLY at
    a threshold selects the higher level, one below stays put — and the
    in-flight term holds the width up after the queue drains (the
    hysteresis that keeps a busy fleet from collapsing mid-burst)."""
    dvfs = QueueDVFS(thresholds=(4, 16), batch_levels=(8, 32, 128))
    q = RequestQueue()

    def width(waiting, in_flight, capacity=None):
        if len(q):
            q.take(len(q))
        q.extend(range(waiting))
        return select_width(dvfs, q, in_flight=in_flight,
                            capacity=capacity)

    assert width(3, 0) == 8                  # one below the first edge
    assert width(4, 0) == 32                 # exactly at it -> climb
    assert width(15, 0) == 32
    assert width(16, 0) == 128               # second edge, same rule
    # the same edges driven purely by in-flight sessions
    assert width(0, 3) == 8
    assert width(0, 4) == 32
    assert width(0, 16) == 128
    # split across both terms: 2 waiting + 2 resident touches the edge
    assert width(2, 2) == 32
    assert width(2, 1) == 8
    # capacity clamps the ladder, never raises it
    assert width(16, 0, capacity=12) == 12
    assert width(3, 0, capacity=12) == 8


def test_session_table_compaction():
    t = SessionTable(capacity=4)
    ss = [Session(sid=i, stream=None, total_ticks=1) for i in range(3)]
    assert [t.admit(s) for s in ss] == [0, 1, 2]
    evicted, moved_from = t.evict(0)     # tail (slot 2) fills the hole
    assert evicted.sid == 0 and moved_from == 2
    assert [s.sid for s in t.slots] == [2, 1]
    evicted, moved_from = t.evict(1)     # tail itself: no move
    assert evicted.sid == 1 and moved_from is None
    assert t.evict_tail().sid == 2 and t.n_active == 0


def test_poisson_traffic_deterministic_and_exhausts():
    a = PoissonTraffic(rate=2.0, n_sessions=9, seed=5)
    b = PoissonTraffic(rate=2.0, n_sessions=9, seed=5)
    got = []
    while not a.exhausted:
        got.extend(a.poll())
    assert len(got) == 9 and a.poll() == []
    assert [s.sid for s in got] == list(range(9))
    assert got == b.drain()              # same seed, same arrivals
    lo, hi = a.tick_range
    assert all(lo <= s.total_ticks <= hi for s in got)


# ------------------------------------------------------- bitwise anchors

@pytest.mark.parametrize("name", ["adaptive", "kws"])
def test_fleet_of_one_bitwise_matches_chipsim(adaptive_sc, name):
    """Acceptance anchor: w=1 fleet == plain ChipSim.run, bitwise."""
    sc = adaptive_sc if name == "adaptive" else kws_scenario(
        n_pairs=2, n_neurons=32, hidden=8, n_keywords=3)
    T = 3 * TC
    sess = _solo(sc, seed=41, total_ticks=T)
    # plain engine: same program shape, whole stimulus preloaded
    stim = sc.stream(41).segment(0, T)
    recs = ChipSim(compile_graph(sc.graph(T, stim))).run(T)
    for k in sc.output_keys:
        np.testing.assert_array_equal(sess.outputs[k], np.asarray(recs[k]))


@pytest.mark.parametrize("network", ["adaptive", "farm"])
def test_streamed_stimulus_matches_table(network):
    """One tick per network: the window streamed through ``state["stim"]``
    drives it exactly as the same table baked in, read at phase step 0
    (every channel on the same tick), so served and benchmarked programs
    run the same semantics."""
    ens = build_ensemble(32, 1, seed=0)
    table = TableStimulus.sine(ens, 48, 0.8, 24.0, phase_step=0)
    window = {"r": np.asarray(table.r, np.float32),
              "drive": np.asarray(table.drive)}
    if network == "adaptive":
        build = lambda stim: adaptive_control_graph(
            2, 32, seed=0, plastic=True, stimulus=stim)
    else:
        build = lambda stim: hybrid_farm_graph(2, 32, 8, seed=0,
                                               stimulus=stim)
    recs = [ChipSim(compile_graph(build(stim))).run(64)
            for stim in (table, StreamedStimulus(window))]
    assert sorted(recs[0]) == sorted(recs[1])
    for k in recs[0]:
        np.testing.assert_array_equal(np.asarray(recs[0][k]),
                                      np.asarray(recs[1][k]), err_msg=k)
    assert np.asarray(recs[0]["n_spk"]).sum() > 0


def test_preemption_and_resume_invisible(adaptive_sc):
    """Sessions preempted by fleet narrowing finish with outputs equal
    to their uninterrupted solo runs (learn state included — the
    adaptive scenario's decoders ride the checkpointed carry).

    Equality here is float-tolerance, not bitwise: narrowing by design
    changes the vmap width, and XLA reassociates batched reductions
    differently per width (~1e-7 relative).  Bitwise invariance at FIXED
    width is pinned by the fleet-of-one and suspend/restore tests."""
    sc = adaptive_sc
    totals = [2 * TC, 5 * TC, 5 * TC]
    tr = PoissonTraffic(rate=10.0, n_sessions=3, seed=2,
                        tick_range=(1, 1))       # lengths patched below
    specs = tr.drain()
    sessions = [Session(sid=sp.sid, stream=sc.stream(sp.seed),
                        total_ticks=totals[sp.sid]) for sp in specs]
    # levels (1, 4) with threshold 3: all three admitted wide; once the
    # short session completes, offered load 2 < 3 narrows the fleet to 1,
    # preempting a tail session mid-run
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(3,), batch_levels=(1, 4)))
    out = eng.serve(None, sessions=sessions)
    assert out["stats"]["completed"] == 3
    assert out["stats"]["preemptions"] >= 1
    for sess in out["sessions"]:
        ref = _solo(sc, seed=specs[sess.sid].seed,
                    total_ticks=sess.total_ticks)
        for k in sc.output_keys:
            np.testing.assert_allclose(sess.outputs[k], ref.outputs[k],
                                       rtol=3e-6, atol=1e-7)


def test_suspend_restore_cross_engine_bitwise(adaptive_sc, tmp_path):
    """Engine 1 serves two rounds and suspends (checkpoint through
    repro.ckpt); a FRESH engine restores the session from disk and
    finishes it — the stitched outputs equal the uninterrupted run."""
    sc = adaptive_sc
    T, seed = 5 * TC, 99
    ref = _solo(sc, seed, T)

    kw = dict(round_ticks=TC, capacity=1, ckpt_dir=tmp_path,
              dvfs=QueueDVFS(thresholds=(2,), batch_levels=(1, 1)))
    eng1 = FleetEngine(sc, max_rounds=2, **kw)
    s1 = Session(sid=7, stream=sc.stream(seed), total_ticks=T)
    eng1.serve(None, sessions=[s1])
    assert s1.ticks_done == 2 * TC and not s1.done
    assert [s.sid for s in eng1.suspend()] == [7]
    part1 = {k: np.concatenate(v) for k, v in s1.outputs.items()}

    eng2 = FleetEngine(sc, **kw)
    s2 = eng2.restore_session(7, stream=sc.stream(seed), total_ticks=T)
    assert s2.ticks_done == 2 * TC
    done = eng2.serve(None, sessions=[s2])["sessions"][0]
    assert done.done
    for k in sc.output_keys:
        stitched = np.concatenate([part1[k], done.outputs[k]])
        np.testing.assert_array_equal(stitched, ref.outputs[k])


# ------------------------------------------------------------ scheduling

def test_width_follows_queue_depth(adaptive_sc):
    """A burst of arrivals widens the fleet to a higher batch level; the
    drain narrows it back down — both levels appear in the histogram."""
    sc = adaptive_sc
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(3, 6),
                                     batch_levels=(2, 4, 8)))
    tr = PoissonTraffic(rate=8.0, n_sessions=8, seed=0,
                        tick_range=(2 * TC, 4 * TC))
    st = eng.serve(tr)["stats"]
    assert st["completed"] == 8
    widths = {int(k) for k in st["width_hist"]}
    assert max(widths) >= 4 and min(widths) <= 4
    assert set(st["queue"]) >= {"submitted", "taken", "wait_p50_s"}
    assert st["joules_per_request"] > 0.0
    assert st["request_latency_s"]["p99"] >= st["request_latency_s"]["p50"]


def test_fleet_stats_account_every_tick(adaptive_sc):
    sc = adaptive_sc
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(2,), batch_levels=(1, 2)))
    tr = PoissonTraffic(rate=1.0, n_sessions=3, seed=4,
                        tick_range=(TC, 3 * TC))
    out = eng.serve(tr)
    st = out["stats"]
    assert st["ticks_served"] == sum(s.total_ticks
                                     for s in out["sessions"])
    # padded (post-completion) round ticks are accounted separately
    assert st["ticks_run"] >= st["ticks_served"]
    for s in out["sessions"]:
        assert s.response is not None and "final_err" in s.response
        assert s.energy_j > 0.0 and s.latency_s() > 0.0


# -------------------------------------------------- scenarios and boards

def test_kws_fleet_end_to_end():
    sc = kws_scenario(n_pairs=2, n_neurons=32, hidden=8, n_keywords=3)
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(2, 5),
                                     batch_levels=(2, 4, 8)))
    tr = PoissonTraffic(rate=2.0, n_sessions=6, seed=3,
                        tick_range=(TC, 3 * TC))
    out = eng.serve(tr)
    assert out["stats"]["completed"] == 6
    for s in out["sessions"]:
        assert len(s.response["scores"]) == 8
        assert 0 <= s.response["top_unit"] < 8
        assert s.outputs["hidden_out"].shape == (s.total_ticks, 2, 8)


def test_board_fleet_smoke(adaptive_sc):
    """The engine never looks inside the program: a board-compiled
    adaptive graph (chip-crossing control loops) serves unchanged."""
    from repro.board import BoardSpec
    sc = adaptive_scenario(n_channels=2, n_neurons=24)
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(2,), batch_levels=(1, 2)),
                      board=BoardSpec.parse("2x1", chip="2x2"),
                      refine=False)
    tr = PoissonTraffic(rate=1.0, n_sessions=2, seed=1,
                        tick_range=(TC, 2 * TC))
    out = eng.serve(tr)
    assert out["stats"]["completed"] == 2
    assert all(s.energy_j > 0 for s in out["sessions"])


def test_batched_probes_ride_the_fleet(adaptive_sc):
    """Per-instance probe accumulators travel with sessions through the
    batched carry and come back per-session at completion.  Sessions
    emit samples at the stride boundaries their own timeline crosses
    (a session shorter than ``probe_ticks`` leaves later windows empty),
    so fleet probes use strides <= the session length."""
    from repro.obs import ProbeSpec
    sc = adaptive_sc
    eng = FleetEngine(sc, round_ticks=TC,
                      dvfs=QueueDVFS(thresholds=(2,), batch_levels=(1, 2)),
                      probes=(ProbeSpec("pl_mean", "pl", "mean", stride=TC),
                              ProbeSpec("e_sum", "e_dvfs_baseline", "sum",
                                        stride=TC)),
                      probe_ticks=4 * TC)
    tr = PoissonTraffic(rate=2.0, n_sessions=3, seed=6,
                        tick_range=(2 * TC, 4 * TC))
    out = eng.serve(tr)
    assert out["stats"]["completed"] == 3
    for s in out["sessions"]:
        pr = s.outputs["probes"]
        n_win = s.ticks_run // TC               # windows this session ran
        assert pr["pl_mean"].shape[0] == 4      # probe_ticks // stride
        assert np.all(pr["pl_mean"][:n_win] >= 0.0)
        assert pr["e_sum"][:n_win].sum() > 0.0
        assert np.all(pr["e_sum"][n_win:] == 0.0)   # windows never reached


@pytest.mark.parametrize("op,stride", [("peak", 8), ("mean", 8), ("sum", 5),
                                       ("last", 8), ("ema", None)])
def test_batched_probe_step_equals_per_instance(op, stride):
    """Deterministic twin of the hypothesis property in
    test_obs_property.py (which skips when hypothesis is absent): the
    batched probe fold over B instances with distinct local tick
    counters equals B independent unbatched folds, bitwise."""
    import jax
    import jax.numpy as jnp
    from repro.obs import ProbeSpec
    from repro.obs.probes import (make_batched_probe_step, make_probe_step,
                                  n_probe_samples)

    batch, n_ticks, n_steps = 3, 24, 14
    offs = np.asarray([0, 5, 17], np.int32)
    rng = np.random.default_rng(9)
    sig = rng.uniform(0.0, 8.0, (batch, n_steps, 4)).astype(np.float32)
    specs = (ProbeSpec("p", "sig", op, stride=stride, alpha=0.25),)
    shapes = {"sig": jax.ShapeDtypeStruct((4,), jnp.float32)}

    init, step, fin = make_probe_step(specs, shapes, n_ticks)
    binit, bstep, bfin = make_batched_probe_step(specs, shapes, n_ticks,
                                                 batch)
    obs_b = binit
    for j in range(n_steps):
        obs_b = bstep(obs_b, {"sig": jnp.asarray(sig[:, j])},
                      jnp.asarray(offs + j))
    out_b = np.asarray(bfin(obs_b)["p"])
    assert out_b.shape == (batch, n_probe_samples(n_ticks, stride), 4)
    for i in range(batch):
        obs = init
        for j in range(n_steps):
            obs = step(obs, {"sig": jnp.asarray(sig[i, j])},
                       jnp.int32(int(offs[i]) + j))
        np.testing.assert_array_equal(out_b[i], np.asarray(fin(obs)["p"]))
