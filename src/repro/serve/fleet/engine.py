"""The fleet engine: vmapped board instances under user traffic.

One compiled ``ChipProgram`` (or board program — the engine never looks
inside), N resident user sessions, one ``jax.vmap`` over the engine's
per-tick step: the batched scan carry holds every session's full state
(membrane/learn/stimulus), and a scheduling round advances all resident
sessions ``round_ticks`` ticks in a single jitted scan of the batched
body.  Between rounds the host does admission control:

* arrivals from the load generator land in the shared ``RequestQueue``
  (``repro.serve.queue`` — the same class the LM ``ServeEngine`` drains);
* the queue's offered load (waiting + resident) runs through
  ``QueueDVFS`` — the paper's spike-FIFO -> performance-level loop — to
  pick the target fleet width.  Bursts widen the batch (jit retraces
  once per width, then it's cached); a draining queue narrows it,
  preempting tail sessions: their carry slice is checkpointed through
  ``repro.ckpt`` and they re-queue at the head, resuming bit-identically
  later (possibly in a different slot, or a different engine process);
* admitted sessions stream their input in per round (``state["stim"]``
  is swapped with each session's next stimulus window — host -> device
  streaming through the carry) and their per-tick outputs stream back
  out of the scan.

A fleet of width 1 is the plain engine: the batched body at w=1 runs
the exact ``ChipSim.run`` tick, which the tier-1 suite pins bitwise.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.stimulus import blank_stim
from repro.ckpt.checkpoint import CheckpointManager
from repro.core.dvfs import QueueDVFS
from repro.obs.health import SloMonitor, default_fleet_slos
from repro.obs.metrics import (MetricsRegistry, device_metrics_for,
                               make_device_metrics)
from repro.obs.probes import make_batched_probe_step, resolve_probes
from repro.obs.spans import SpanLog, validate_spans
from repro.serve.fleet.scenarios import ServedScenario
from repro.serve.fleet.sessions import Session, SessionTable
from repro.serve.queue import RequestQueue, percentiles

# the engine's simulated-energy tiers, summed per instance per tick
# (DVFS datapath + NoC traffic + learning engine when plastic)
ENERGY_KEYS = ("e_dvfs_baseline", "e_dvfs_neuron", "e_dvfs_synapse",
               "e_noc", "e_learn")


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


@dataclass
class FleetObs:
    """The serving tier's observability bundle: one span log (request
    lifecycles + per-round fleet counters), one metrics registry
    (host-side scheduler/queue numbers + device-side scan accumulators),
    and one SLO monitor evaluated per scheduling round.  ``FleetEngine``
    accepts ``obs=FleetObs()`` (or ``obs=True`` for this default
    configuration); with ``obs=None`` — the default — NO observability
    code runs and the serve results are bitwise identical to the
    pre-observability engine."""
    spans: SpanLog = field(default_factory=SpanLog)
    metrics: MetricsRegistry = field(default_factory=MetricsRegistry)
    slos: tuple = field(default_factory=default_fleet_slos)
    device_metrics: tuple = None          # None = standard fleet set
    monitor: SloMonitor = None

    def __post_init__(self):
        if self.monitor is None:
            self.monitor = SloMonitor(self.slos, spans=self.spans)


class FleetEngine:
    """Serve a ``ServedScenario`` with a width-elastic vmapped fleet."""

    def __init__(self, scenario: ServedScenario, *, round_ticks: int = 64,
                 dvfs: Optional[QueueDVFS] = None,
                 capacity: Optional[int] = None, probes=(),
                 probe_ticks: int = 1024, board=None, refine: bool = True,
                 ckpt_dir=None, seed: int = 1, keep_outputs: bool = True,
                 max_rounds: int = 100_000, exec_mode: str = "auto",
                 obs: "FleetObs | bool | None" = None):
        self.scenario = scenario
        self.Tc = int(round_ticks)
        self.dvfs = dvfs or QueueDVFS()
        self.ckpt_dir = None if ckpt_dir is None else Path(ckpt_dir)
        self.keep_outputs = keep_outputs
        self.max_rounds = max_rounds
        self.obs = FleetObs() if obs is True else (obs or None)

        graph = scenario.graph(self.Tc)
        if board is not None:
            from repro.board import compile_board
            self.program = compile_board(graph, board, refine=refine)
        else:
            self.program = compile_graph(graph)
        # exec_mode reaches the vmapped stepper unchanged ("auto" | "dense"
        # | "event"): per-tick records are bitwise-identical either way, so
        # serving results don't depend on the mode.  Note the compressed
        # tick's overflow fallback is a lax.cond, and under vmap XLA
        # evaluates BOTH branches — a vmapped event fleet is correct but
        # only saves the work the compressed branch itself skips; the
        # single-instance speedup story lives in ChipSim.run.
        self.sim = ChipSim(self.program, exec_mode=exec_mode)
        # the program's weights/tables ride every round as an argument
        self._template, self._tick, self._params = self.sim.make_stepper(
            seed=seed)

        self.capacity = int(capacity or max(self.dvfs.batch_levels))
        self.levels = sorted({min(int(l), self.capacity)
                              for l in self.dvfs.batch_levels})

        self._rec_sd = jax.eval_shape(
            self._tick, self._params, self._template,
            jax.ShapeDtypeStruct((), jnp.int32))[1]
        self.energy_keys = tuple(k for k in ENERGY_KEYS
                                 if k in self._rec_sd)
        self.output_keys = tuple(scenario.output_keys)
        missing = [k for k in self.output_keys if k not in self._rec_sd]
        if missing:
            raise KeyError(f"scenario output keys {missing} not in this "
                           f"program's rec; have {sorted(self._rec_sd)}")

        self.probe_specs = resolve_probes(self.program, probes)
        self.probe_ticks = int(probe_ticks)
        if self.probe_specs:
            binit1, _, fin = make_batched_probe_step(
                self.probe_specs, self._rec_sd, self.probe_ticks, 1)
            self._obs_template = _tree_map(lambda x: x[0], binit1)
            self._obs_fin = fin
        else:
            self._obs_template, self._obs_fin = {}, None

        self._blank = blank_stim(scenario.ens, self.Tc)
        self._rounds: dict = {}
        # device-side metric accumulators ride the round scan only when
        # observability is on; the spec set is filtered against this
        # program's actual rec keys once, here
        if self.obs is not None:
            self._dev_specs = (
                device_metrics_for(self._rec_sd)
                if self.obs.device_metrics is None
                else device_metrics_for(self._rec_sd,
                                        self.obs.device_metrics))
            self.obs.spans.meta.setdefault("scenario", scenario.name)
            self.obs.spans.meta.setdefault("round_ticks", self.Tc)
            self.obs.spans.meta.setdefault(
                "levels", [int(l) for l in self.levels])
        else:
            self._dev_specs = ()
        self.queue = RequestQueue(
            spans=None if self.obs is None else self.obs.spans)
        self.table = SessionTable(self.capacity)
        self._carry = None              # {"st": batched, "obs": batched}

    # ------------------------------------------------------------ rounds
    def _round_fn(self, w: int):
        """The jitted scheduling round at width ``w`` (cached per width):
        scan ``Tc`` ticks of the vmapped engine step, stream out the
        scenario's output signals, each instance's per-tick joules and —
        when observability is on — the round's device-metric totals."""
        fn = self._rounds.get(w)
        if fn is not None:
            return fn
        Tc, out_keys, e_keys = self.Tc, self.output_keys, self.energy_keys
        vtick = jax.vmap(self._tick, in_axes=(None, 0, 0))
        if self.probe_specs:
            _, pstep, _ = make_batched_probe_step(
                self.probe_specs, self._rec_sd, self.probe_ticks, w)
        else:
            pstep = None
        if self._dev_specs:
            dinit, dstep = make_device_metrics(self._dev_specs, w)
        else:
            dinit, dstep = {}, None

        def run_round(params, carry, t0s):
            def body(c, i):
                ts = t0s + i                       # per-instance local tick
                st, rec = vtick(params, c["st"], ts)
                obs = pstep(c["obs"], rec, ts) if pstep else c["obs"]
                met = dstep(c["met"], rec) if dstep else c["met"]
                out = {k: rec[k] for k in out_keys}
                e = jnp.zeros(t0s.shape[0])
                for k in e_keys:
                    v = rec[k]
                    e = e + v.sum(axis=tuple(range(1, v.ndim)))
                return {"st": st, "obs": obs, "met": met}, (out, e)
            # the device-metric accumulators reset every round: they ride
            # the scan-internal carry, never the persistent fleet carry,
            # so observability on/off cannot change widths or snapshots
            cc = {"st": carry["st"], "obs": carry["obs"], "met": dinit}
            cc, (outs, es) = jax.lax.scan(body, cc, jnp.arange(Tc))
            return ({"st": cc["st"], "obs": cc["obs"]}, outs, es,
                    cc["met"])

        fn = jax.jit(run_round)
        self._rounds[w] = fn
        return fn

    def width_for(self, n_active: int) -> int:
        """Smallest batch level covering ``n_active`` residents."""
        for l in self.levels:
            if l >= n_active:
                return l
        return self.levels[-1]

    # ----------------------------------------------- batched carry admin
    def _fresh_carry(self, w: int) -> dict:
        bc = lambda tmpl: _tree_map(
            lambda x: jnp.broadcast_to(x, (w,) + x.shape), tmpl)
        return {"st": bc(self._template), "obs": bc(self._obs_template)}

    def _ensure_width(self, w: int) -> None:
        if self._carry is None:
            self._carry = self._fresh_carry(w)
            return
        cur = jax.tree_util.tree_leaves(self._carry["st"])[0].shape[0]
        if cur == w:
            return

        def fix(x, tmpl):
            if x.shape[0] >= w:
                return x[:w]
            pad = jnp.broadcast_to(tmpl, (w - x.shape[0],) + tmpl.shape)
            return jnp.concatenate([x, pad], axis=0)
        self._carry = {
            "st": _tree_map(fix, self._carry["st"], self._template),
            "obs": _tree_map(fix, self._carry["obs"], self._obs_template),
        }

    def _gather(self, slot: int) -> dict:
        """Session snapshot: slot ``slot`` of every carry leaf, on host."""
        return _tree_map(lambda x: np.asarray(x[slot]), self._carry)

    def _scatter(self, slot: int, snap: dict) -> None:
        self._carry = _tree_map(
            lambda b, s: b.at[slot].set(jnp.asarray(s)), self._carry, snap)

    def _move_slot(self, dst: int, src: int) -> None:
        self._carry = _tree_map(lambda x: x.at[dst].set(x[src]),
                                self._carry)

    # ------------------------------------------------ checkpoint/restore
    def _ckpt_mgr(self, sid: int) -> CheckpointManager:
        return CheckpointManager(self.ckpt_dir / f"s{sid:06d}", keep=1,
                                 async_save=False)

    def _store(self, sess: Session, snap: dict) -> None:
        if self.ckpt_dir is None:
            sess.snapshot = snap
        else:
            self._ckpt_mgr(sess.sid).save(
                sess.ticks_done, snap,
                meta={"sid": sess.sid, "ticks_done": sess.ticks_done,
                      "scenario": self.scenario.name})
            sess.ckpt_step = sess.ticks_done

    def _load(self, sess: Session) -> dict:
        template = {"st": self._template, "obs": self._obs_template}
        if self.ckpt_dir is not None and sess.ticks_done > 0:
            tree, manifest = self._ckpt_mgr(sess.sid).restore(template)
            if tree is not None:
                sess.ticks_done = int(manifest["meta"].get(
                    "ticks_done", sess.ticks_done))
                return tree
        if sess.snapshot is not None:
            return sess.snapshot
        return template                   # fresh session

    def suspend(self) -> list:
        """Checkpoint and evict every resident session (graceful engine
        shutdown / drain).  Returns the suspended sessions; with a
        ``ckpt_dir`` a different engine process can pick each one up via
        ``restore_session`` and continue bit-identically."""
        out = []
        while self.table.n_active:
            sess = self.table.evict_tail()
            self._store(sess, self._gather(self.table.n_active))
            if self.obs is not None:
                self.obs.spans.emit(
                    "suspend", sess.sid, ticks_done=sess.ticks_done,
                    ckpt="disk" if self.ckpt_dir is not None else "memory")
            out.append(sess)
        return out

    def restore_session(self, spec_or_sid, stream=None,
                        total_ticks: int = 0) -> Session:
        """Re-open a checkpointed session in THIS engine (possibly a
        different process than the one that evicted it): reads the
        session's latest checkpoint meta and queues it for admission."""
        sid = getattr(spec_or_sid, "sid", spec_or_sid)
        mgr = self._ckpt_mgr(sid)
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint for session {sid}")
        sess = Session(sid=sid,
                       stream=stream or self.scenario.stream(sid),
                       total_ticks=total_ticks)
        sess.ticks_done = step
        sess.ckpt_step = step
        return sess

    # -------------------------------------------------------- the server
    def _admit_specs(self, specs, t_base: float) -> None:
        for spec in specs:
            self.queue.submit(Session(
                sid=spec.sid, stream=self.scenario.stream(spec.seed),
                total_ticks=spec.total_ticks,
                arrival_s=time.perf_counter() - t_base))

    def serve(self, traffic, *, sessions=None) -> dict:
        """Drive the fleet until ``traffic`` is exhausted and every
        session has completed.  ``sessions`` optionally seeds the queue
        with pre-built ``Session`` objects (e.g. checkpointed resumes)
        ahead of generated arrivals."""
        t0 = time.perf_counter()
        obs = self.obs
        for s in (sessions or []):
            s.arrival_s = time.perf_counter() - t0
            self.queue.submit(s)
        completed: list = []
        width_hist: dict = {}
        tick_lat_s: list = []
        rounds = 0

        while rounds < self.max_rounds:
            rounds += 1
            if traffic is not None:
                self._admit_specs(traffic.poll(), t0)
            exhausted = traffic is None or traffic.exhausted

            target = min(self.capacity, self.dvfs.batch_size(
                self.queue.peek_depth_with(self.table.n_active)))
            # narrow: preempt tail sessions (checkpoint + requeue front)
            while self.table.n_active > target:
                sess = self.table.evict_tail()
                self._store(sess, self._gather(self.table.n_active))
                sess.preemptions += 1
                if obs is not None:
                    obs.spans.emit(
                        "preempt", sess.sid, round_i=rounds - 1,
                        slot=self.table.n_active, target=target,
                        ticks_done=sess.ticks_done,
                        ckpt="disk" if self.ckpt_dir is not None
                        else "memory")
                    obs.metrics.counter("preempted").inc()
                self.queue.submit(sess, front=True)
            # widen: admit from the queue into compact slots
            while self.table.n_active < target and self.queue:
                sess = self.queue.take(1)[0]
                self._ensure_width(self.width_for(self.table.n_active + 1))
                slot = self.table.admit(sess)
                if sess.admitted_s is None:
                    sess.admitted_s = time.perf_counter() - t0
                self._scatter(slot, self._load(sess))
                sess.snapshot = None
                if obs is not None:
                    # a session with served ticks is resuming (it was
                    # preempted here, or restored from another engine's
                    # checkpoint); a fresh session is admitted
                    kind = "resume" if sess.ticks_done > 0 else "admit"
                    obs.spans.emit(kind, sess.sid, round_i=rounds - 1,
                                   slot=slot, width=target,
                                   ticks_done=sess.ticks_done)
                    obs.metrics.counter(
                        "resumed" if kind == "resume" else "admitted").inc()

            n_active = self.table.n_active
            if n_active == 0:
                if exhausted and not self.queue:
                    break
                continue
            w = self.width_for(n_active)
            self._ensure_width(w)
            width_hist[w] = width_hist.get(w, 0) + 1

            # stream this round's stimulus windows into the carry
            segs = [s.stream.segment(s.ticks_done, self.Tc)
                    for s in self.table.slots]
            segs += [self._blank] * (w - n_active)
            stim_b = {k: jnp.asarray(np.stack([g[k] for g in segs]))
                      for k in segs[0]}
            st = dict(self._carry["st"])
            st["stim"] = stim_b
            self._carry["st"] = st
            t0s = jnp.asarray([s.ticks_done for s in self.table.slots]
                              + [0] * (w - n_active), jnp.int32)

            wall0 = time.perf_counter()
            self._carry, outs, es, met = self._round_fn(w)(
                self._params, self._carry, t0s)
            es = jax.block_until_ready(es)
            round_s = time.perf_counter() - wall0
            tick_lat_s.append(round_s / self.Tc)

            es_np = np.asarray(es)                       # (Tc, w)
            outs_np = {k: np.asarray(v) for k, v in outs.items()}
            done_slots = []
            for slot, sess in enumerate(self.table.slots):
                use = min(sess.remaining, self.Tc)
                if obs is not None:
                    obs.spans.emit("round", sess.sid, round_i=rounds - 1,
                                   slot=slot, width=w,
                                   t0_ticks=sess.ticks_done, ticks=use,
                                   start_s=wall0 - t0, dur_s=round_s)
                sess.ticks_run += self.Tc
                sess.energy_j += float(es_np[:, slot].sum())
                if self.keep_outputs:
                    for k in self.output_keys:
                        sess.outputs.setdefault(k, []).append(
                            outs_np[k][:use, slot])
                sess.ticks_done += use
                if sess.done:
                    done_slots.append(slot)
            for slot in sorted(done_slots, reverse=True):
                sess = self.table.slots[slot]
                sess.done_s = time.perf_counter() - t0
                if self.keep_outputs:
                    cat = {k: np.concatenate(v)
                           for k, v in sess.outputs.items()}
                    sess.outputs = cat
                    if self._obs_fin is not None:
                        obs_slot = _tree_map(lambda x: x[slot],
                                             self._carry["obs"])
                        sess.outputs["probes"] = {
                            k: np.asarray(v) for k, v in
                            self._obs_fin(obs_slot).items()}
                    if self.scenario.response is not None:
                        sess.response = self.scenario.response(cat)
                _, moved_from = self.table.evict(slot)
                if moved_from is not None:
                    self._move_slot(slot, moved_from)
                completed.append(sess)
                if obs is not None:
                    obs.spans.emit(
                        "complete", sess.sid, round_i=rounds - 1,
                        ticks_done=sess.ticks_done,
                        energy_j=round(sess.energy_j, 9),
                        latency_s=round(sess.latency_s(), 6))
            if obs is not None:
                self._observe_round(obs, rounds - 1, w, n_active, round_s,
                                    es_np, met, completed, t0, wall0)

        wall = time.perf_counter() - t0
        lat = [s.latency_s() for s in completed]
        ticks_served = sum(s.ticks_done for s in completed)
        stats = {
            "completed": len(completed),
            "rounds": rounds,
            "wall_s": wall,
            "sessions_per_s": len(completed) / wall if wall > 0 else 0.0,
            "ticks_served": ticks_served,
            "ticks_run": sum(s.ticks_run for s in completed),
            "ticks_per_s": ticks_served / wall if wall > 0 else 0.0,
            "request_latency_s": percentiles(lat),
            "tick_latency_s": percentiles(tick_lat_s),
            "joules_per_request": (float(np.mean([s.energy_j
                                                  for s in completed]))
                                   if completed else 0.0),
            "preemptions": sum(s.preemptions for s in completed),
            "width_hist": {str(k): v for k, v in sorted(width_hist.items())},
            "queue": self.queue.stats(),
        }
        result = {"sessions": completed, "stats": stats}
        if obs is not None:
            dropped = len(self.queue) + self.table.n_active
            errors = validate_spans(obs.spans.events)
            stats["health"] = obs.monitor.verdict(dropped=dropped,
                                                  span_errors=errors)
            result["obs"] = {"spans": obs.spans,
                             "metrics": obs.metrics.snapshot(),
                             "health": stats["health"]}
        return result

    # ------------------------------------------------- per-round telemetry
    def _observe_round(self, obs, round_i: int, w: int, n_active: int,
                       round_s: float, es_np, met, completed, t0,
                       wall0) -> None:
        """Fold one scheduling round into the observability bundle:
        fleet counter sample, host/device metrics, SLO check.  Pure
        bookkeeping — nothing here feeds back into scheduling."""
        m = obs.metrics
        tick_us = round_s / self.Tc * 1e6
        round_e = float(es_np[:, :n_active].sum())
        m.counter("rounds").inc()
        m.counter("ticks_run").inc(n_active * self.Tc)
        m.counter("energy_j").inc(round_e)
        m.gauge("width").set(w)
        m.gauge("n_active").set(n_active)
        m.gauge("queue_depth").set(len(self.queue))
        m.histogram("tick_us", scale=1.0).observe(tick_us)
        for s in self._dev_specs:
            vals = np.asarray(met[s.name])[:n_active]
            if s.op == "sum":
                m.counter(f"dev/{s.name}").inc(float(vals.sum()))
            elif vals.size:
                # snapshot suffixes gauges with _peak itself
                m.gauge(f"dev/{s.name}").set(float(vals.max()))
        # completion-derived quantities (latency / energy / throughput)
        elapsed = time.perf_counter() - t0
        n_done = len(completed)
        m.gauge("sessions_per_s").set(n_done / elapsed if elapsed else 0.0)
        admitted = m.counter("admitted").value
        m.gauge("preempt_rate").set(
            m.counter("preempted").value / max(1.0, admitted))
        if n_done:
            m.gauge("mj_per_request").set(
                float(np.mean([s.energy_j for s in completed])) * 1e3)
        lat_hist = m.histogram("req_latency_s", scale=1e-3)
        done_this_round = [s for s in completed
                           if s.done_s is not None
                           and s.done_s >= wall0 - t0]
        for sess in done_this_round:
            lat_hist.observe(sess.latency_s())
        obs.spans.sample(round_i, width=w, n_active=n_active,
                         queue_depth=len(self.queue),
                         tick_us=round(tick_us, 3),
                         round_s=round(round_s, 6),
                         energy_j=round(round_e, 9),
                         completed=len(completed))
        obs.monitor.check(m.snapshot(), round_i=round_i)
