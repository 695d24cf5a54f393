"""``ChipSim`` — the workload-agnostic chip engine.

A virtual SpiNNaker2 chip: W x H QPE mesh of PEs running any compiled
``ChipProgram`` (SNN, DNN or hybrid — see ``repro.chip.graph`` /
``repro.chip.compile``) in one ``jax.lax.scan`` over 1 ms ticks.

The program's ``TickSemantics`` advances all PEs as batched axes of the
same arrays and reports per-PE activity (packets emitted, performance
level, Eq. (1) energy split); what the engine adds per tick is the NoC:
each source's packet count hits its precomputed multicast-tree incidence
— either the dense einsum over the (P, n_links) tensor or, once trees are
sparse relative to the mesh (the board-scale regime), a gather +
segment-sum over the CSR entries (``repro.kernels.link_load``) — yielding
per-link loads in packets AND in DNoC flits, so graded-payload
(multi-flit) packets are priced correctly, plus the energy/congestion
accounting from ``NocSpec``.  The representation is auto-selected from
the incidence shape — mesh size, density, per-link fan-in
(``noc_mode="auto"``; force with "dense"/"sparse") — both paths agree
bitwise on integer packet counts.
No per-source Python in the hot path, no per-workload branches in the
engine.

Everything the tick reads that is not its carry — the semantics'
synaptic weights and drive tables, the NoC incidence or plan — is an
ARGUMENT of the compiled program, never a constant inside it
(``hoist_constants``): a 4096-PE synfire ring holds ~1 GB of weights,
which as constants would make a ~1 GB executable.  Each (program,
settings) pair traces and compiles once per ``ChipSim``.

``chip_power_table`` generalizes ``synfire_power_table`` from one PE
average to the whole chip: per-PE table + chip totals + NoC power + the
SpiNNCer-style peak-link-load bottleneck check.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.chip.compile import ChipProgram
from repro.chip.mesh_noc import (DENSE_DENSITY, MAX_SPARSE_COLS,
                                 MIN_SPARSE_LINKS, MeshNoc,
                                 SPIKE_PACKET_BITS)
from repro.core.dvfs import DVFSController
from repro.core.energy import PEEnergyModel


def hoist_constants(fn: Callable, *example_args) -> tuple:
    """Trace ``fn(*example_args)`` once and lift every array it closes
    over into an explicit argument.

    Returns ``(consts, pure)`` with ``pure(consts, *args) == fn(*args)``
    for args shaped like ``example_args`` (arrays or
    ``ShapeDtypeStruct``s).  ``consts`` holds the closed-over arrays in
    trace order; ``pure`` closes over nothing but the traced program, so
    a ``jax.jit`` of it takes the arrays as arguments instead of baking
    them into the executable."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    in_tree = jax.tree.structure(example_args)
    out_tree = jax.tree.structure(out_shape)
    jaxpr = closed.jaxpr

    def pure(consts, *args):
        leaves, tree = jax.tree.flatten(args)
        if tree != in_tree:
            raise ValueError(f"argument structure {tree} does not match "
                             f"the traced {in_tree}")
        return jax.tree.unflatten(
            out_tree, jax.core.eval_jaxpr(jaxpr, consts, *leaves))

    return tuple(closed.consts), pure


@dataclass
class ChipSim:
    """A compiled workload program on a full PE mesh (or, for a
    ``repro.board.BoardProgram``, a whole multi-chip board — the engine
    is identical; only the incidence and the NoC pricing differ).

    ``noc_mode`` selects the NoC accounting representation: "auto" picks
    sparse vs dense by incidence density, "sparse"/"dense" force it (the
    two agree bitwise — forcing is for benchmarks and golden tests).
    ``link_load_impl`` overrides the program NoC's sparse accumulation
    kernel (None defers to the NoC's own knob: "auto" -> the column
    plan; "pallas" -> the prefix-sum kernel).

    ``exec_mode`` selects the execution mode: "dense" runs the per-PE
    work of every tick at full width; "event" runs the workload's
    activity-compressed tick (when its semantics provides one —
    ``make_event_tick``) and the event-mode NoC accounting; "auto" picks
    event exactly when the NoC auto-select goes sparse (the same
    board-scale regime).  Event mode is bitwise-identical to dense on
    every record — rasters, probes, energies — by construction; the
    compressed tick falls back to the dense formulas inside the scan
    whenever a tick's activity overflows the event buffer.
    ``event_impl`` picks the event NoC kernel (``repro.kernels.
    event_gather``: "auto" delegates to the column plan;
    "gather"/"pallas" force the compacted-index variants).
    """
    program: ChipProgram
    dvfs: Optional[DVFSController] = None
    em: PEEnergyModel = field(default_factory=PEEnergyModel)
    noc_mode: str = "auto"
    link_load_impl: Optional[str] = None
    exec_mode: str = "auto"
    event_impl: Optional[str] = None
    # (init, step, params) per stepper settings, and the compiled scans
    # of ``run`` per (settings, n_ticks[, probes, keep_records]), each
    # with its extra arguments and probe finalizer: each program
    # compiles once
    _steppers: dict = field(default_factory=dict, init=False, repr=False,
                            compare=False)
    _runs: dict = field(default_factory=dict, init=False, repr=False,
                        compare=False)

    def __post_init__(self):
        if self.dvfs is None:
            # workload semantics may carry their own FIFO thresholds (e.g.
            # a synfire net built with custom l_th1/l_th2); fall back to
            # the paper's Table II defaults
            sem = self.program.graph.semantics
            make = getattr(sem, "dvfs_controller", None)
            self.dvfs = make() if make else DVFSController()

    @property
    def noc(self) -> MeshNoc:
        return self.program.noc

    def use_sparse_noc(self, noc_mode: str | None = None) -> bool:
        """Resolve the accounting representation for this program.

        Auto requires a big-enough mesh (below ~256 PEs the dense einsum
        is a trivially small GEMV that wins on op overhead), a sparse
        incidence (density), AND a bounded per-link fan-in: the column
        plan unrolls one op per column, so an all-to-one graph — sparse
        by density — would still trace an O(P)-op tick body."""
        mode = noc_mode or self.noc_mode
        if mode not in ("auto", "sparse", "dense"):
            raise ValueError(f"unknown noc_mode {mode!r}")
        if mode == "auto":
            sinc = self.program.sinc
            return (sinc.n_links >= MIN_SPARSE_LINKS
                    and sinc.density <= DENSE_DENSITY
                    and sinc.max_fan_in <= MAX_SPARSE_COLS)
        return mode == "sparse"

    def use_event_mode(self, exec_mode: str | None = None) -> bool:
        """Resolve the execution mode for this program: "auto" picks the
        activity-compressed mode exactly when the NoC auto-select goes
        sparse — the same mesh-scale/density regime where per-tick dense
        work dominates and activity is sparse relative to it."""
        mode = exec_mode or self.exec_mode
        if mode not in ("auto", "event", "dense"):
            raise ValueError(f"unknown exec_mode {mode!r}")
        if mode == "auto":
            return self.use_sparse_noc("auto")
        return mode == "event"

    def make_stepper(self, seed: int = 1, noc_mode: str | None = None,
                     link_load_impl: str | None = None,
                     exec_mode: str | None = None) -> tuple:
        """The batched-carry entry point: ``(init_state, step, params)``
        where ``step(params, state, t) -> (state, rec)`` is the engine's
        FULL per-tick body — semantics tick, on-mesh learning, NoC
        accounting (sparse or dense, tiered for boards) — exactly as
        ``run`` scans it.  ``params`` is the tuple of arrays the body
        reads besides its carry (weights, drive tables, NoC plan); pass
        it through ``jax.jit`` as an argument, not as a closure.

        ``run`` itself is ``lax.scan(partial(step, params), init,
        arange(n_ticks))``, so anything that composes ``step``
        differently — the serving tier's ``jax.vmap`` over a fleet of
        independent instances (``repro.serve.fleet``), chunked stepping
        with checkpoint / restore of the carry between chunks,
        interleaved host I/O — computes bit-identical per-tick records
        to a plain ``run`` of the same program.  The carry returned by
        ``step`` is the full engine state (workload state incl. the
        ``learn`` subtree), which is what ``repro.ckpt`` snapshots for
        session save/restore.  Built once per settings and cached.
        """
        key = (seed, noc_mode, link_load_impl, exec_mode)
        if key not in self._steppers:
            with jax.profiler.TraceAnnotation("chip.build") as span:
                init, chip_tick = self._chip_tick(seed, noc_mode,
                                                  link_load_impl, exec_mode)
                params, step = hoist_constants(
                    chip_tick, init, jax.ShapeDtypeStruct((), jnp.int32))
                # workload semantics may name settings of their own
                # (e.g. the synfire ring's synaptic slab width)
                build_args = getattr(self.program.graph.semantics,
                                     "build_args", None)
                if build_args:
                    span.set_metadata(**build_args(self.program))
            self._steppers[key] = (init, step, params)
        return self._steppers[key]

    def _chip_tick(self, seed, noc_mode, link_load_impl, exec_mode):
        """``(init_state, chip_tick)``: the per-tick body as a closure
        over this program's arrays (``make_stepper`` hoists them)."""
        prog = self.program
        event = self.use_event_mode(exec_mode)
        key = jax.random.PRNGKey(seed)
        tick = None
        if event:
            # the workload's activity-compressed tick; semantics without
            # one run their dense tick under event-mode NoC/activity
            # accounting (records stay bitwise-identical either way)
            tick = prog.make_event_tick(dvfs=self.dvfs, em=self.em, key=key)
        if tick is None:
            tick = prog.make_tick(dvfs=self.dvfs, em=self.em, key=key)
        noc = self.noc
        # on-mesh learning: programs with plastic projections extend the
        # scan carry with per-slot weight/trace state, updated right after
        # the semantics' tick and priced into a per-PE e_learn record.
        # Frozen programs (learn_slots == ()) skip this entirely — the
        # traced tick body is EXACTLY the pre-plasticity engine's.
        # (import here: repro.learn.engine reaches back into repro.chip
        # for the shared energy helpers)
        if getattr(prog, "learn_slots", ()):
            from repro.learn.engine import make_learn_step
            learn = make_learn_step(prog)
        else:
            learn = None
        init = prog.init_state()
        if learn is not None and (not isinstance(init, dict)
                                  or "learn" not in init):
            raise ValueError(
                f"graph {prog.graph.name!r} has plastic projections but "
                "its semantics' init_state does not carry a 'learn' "
                "subtree; include repro.learn.init_learn_state(program)")
        # The kernel knob is validated even when the dense einsum wins
        # (a typo'd impl must error, not silently benchmark dense).
        impl = noc.resolve_link_load_impl(link_load_impl
                                          or self.link_load_impl)
        sparse = self.use_sparse_noc(noc_mode)
        if sparse and event:
            plan = noc.event_plan(prog.sinc, impl=self.event_impl)
        elif sparse:
            plan = noc.device_plan(prog.sinc, impl=impl)
        else:
            inc = jnp.asarray(prog.inc)
        # activity telemetry (identical keys + values in both exec modes):
        # per-link tier masks hoisted once, like the incidence.  Empty
        # tiers (a 1x1 board's zero-link xchip tier) are dropped so the
        # record keys — and the 1x1-board == single-chip bitwise
        # guarantee — don't depend on the NoC class.
        n_src = prog.sinc.n_sources
        tier_masks = {tier: jnp.asarray(m)
                      for tier, m in noc.tier_masks().items()
                      if np.asarray(m).any()}
        tree_links = jnp.asarray(prog.energy_tree_links, jnp.float32)
        static_pb = jnp.asarray(prog.payload_bits)
        # tiered (board) NoC: static per-link tier mask + per-source
        # chip-to-chip tree link counts, hoisted like the incidence.
        # A 1x1 board has no chip-to-chip tier — its records (and traced
        # ops) stay exactly the single-chip engine's, keeping the golden
        # anchor bitwise.
        tiered = getattr(noc, "n_xchip_links", 0) > 0
        if tiered:
            xmask = jnp.asarray(noc.xlink_mask, jnp.float32)
            tree_links_x = jnp.asarray(prog.tree_links_x, jnp.float32)

        @jax.named_scope("chip_tick")
        def chip_tick(state, t):
            with jax.named_scope("semantics"):
                state, rec = tick(state, t)
            if learn is not None:
                with jax.named_scope("learn"):
                    lstate, lrec = learn(state["learn"], rec)
                state = {**state, "learn": lstate}
                rec.update(lrec)
            with jax.named_scope("noc"):
                return state, noc_records(rec)

        def noc_records(rec):
            """The tick's record with the engine's NoC accounting and
            activity telemetry added."""
            packets = rec["packets"].astype(jnp.float32)    # (P,)
            pb = rec.get("payload_bits", static_pb)
            if sparse and event:
                rec["link_load"], rec["link_flits"] = noc.event_noc_loads(
                    packets, plan, pb)
            elif sparse:
                rec["link_load"], rec["link_flits"] = noc.noc_loads(
                    packets, plan, pb)
            else:
                rec["link_load"] = noc.link_loads(packets, inc)
                rec["link_flits"] = noc.flit_loads(packets, inc, pb)
            rec["e_noc"] = noc.traffic_energy_j(packets, tree_links, pb)
            # activity telemetry — emitted by BOTH modes from the same
            # packet/load signals, so activity probes read identically
            active = (rec["packets"] > 0).sum(axis=-1).astype(jnp.int32)
            rec["active_sources"] = active
            rec["active_frac"] = active.astype(jnp.float32) / max(n_src, 1)
            hit = (rec["link_load"] > 0).astype(jnp.float32)
            rec["touched_links"] = hit.sum(axis=-1)
            for tier, m in tier_masks.items():
                rec[f"touched_links_{tier}"] = jnp.matmul(
                    hit, m, precision="highest")
            if tiered:
                with jax.named_scope("xchip"):
                    rec["load_xchip"] = (rec["link_load"]
                                         * xmask).sum(axis=-1)
                    rec["flits_xchip"] = (rec["link_flits"]
                                          * xmask).sum(axis=-1)
                    rec["e_noc_xchip"] = noc.xchip_energy_j(
                        packets, tree_links_x, pb)
            return rec

        return init, chip_tick

    def run(self, n_ticks: int, seed: int = 1, noc_mode: str | None = None,
            link_load_impl: str | None = None, exec_mode: str | None = None,
            probes=(), keep_records: bool = True) -> dict:
        """Per-tick records: everything the program's semantics reports
        (spike rasters / layer occupancy / decoded signals, PLs, Eq. (1)
        energies), plus the engine's NoC accounting:

        link_load  (T, n_links) — packets per link per tick
        link_flits (T, n_links) — DNoC flits per link per tick (graded
                                  multi-flit packets weigh more)
        e_noc      (T,)         — NoC traffic energy per tick [J]
        active_sources (T,)     — sources emitting >= 1 packet this tick
        active_frac (T,)        — active_sources / n_sources
        touched_links (T,) + touched_links_<tier> — links carrying any
                                  traffic this tick, total and per tier

        and, when the program has plastic projections (``learn_slots``),
        the learning tier: weights/traces advance in the scan carry each
        tick (``repro.learn.engine``) and

        e_learn    (T, P)       — per-PE learning energy per tick [J]
                                  (MAC-class weight updates + exp-
                                  accelerator trace decays)

        and, when the program's NoC is tiered (a board: on-chip links plus
        chip-to-chip links), the per-tier split:

        load_xchip / flits_xchip (T,) — packet/flit traversals of
                                  chip-to-chip links this tick
        e_noc_xchip (T,)        — chip-to-chip share of e_noc [J]

        ``noc_mode`` overrides the sim's representation choice per run;
        sparse and dense produce bit-identical records, as do the sparse
        kernels selected by ``link_load_impl``, as does the execution
        mode selected by ``exec_mode`` ("event" = activity-compressed
        tick + event NoC accounting; see the class docstring).  For the synfire program
        the neuron dynamics are the SAME tick function the single-chip
        path scans (``make_synfire_tick``), so an 8-PE ChipSim reproduces
        ``simulate_synfire`` rasters bit for bit.

        ``probes`` (``repro.obs.probes``: ProbeSpec instances or registry
        names) compiles strided/windowed telemetry accumulators into the
        scan carry, returned under ``recs["probes"]``.  The probe step
        runs AFTER the tick — it reads records, never state — so probed
        runs produce bit-identical per-tick records, and with the default
        ``probes=()`` the traced tick body (and carry) is EXACTLY the
        bare engine's.  ``keep_records=False`` (probed runs only) drops
        the full (T, ...) per-tick records and returns just the probe
        output — the memory-bounded mode for long board-scale runs.

        Each (settings, n_ticks[, probes, keep_records]) compiles once,
        ahead of time, with the stage of every instruction recorded
        (``repro.obs.scopes``).  Under a profiler trace a call shows as
        the host span ``chip.run`` (args: ``n_ticks``, the resolved
        ``exec_mode`` and ``noc_mode``, ``cached``) holding
        ``chip.build`` (a new stepper; args: the semantics'
        ``build_args(program)``, if it has one), ``chip.compile`` (a new
        program)
        and ``chip.dispatch``.
        """
        settings = (seed, noc_mode, link_load_impl, exec_mode)
        if not probes:
            if not keep_records:
                raise ValueError("keep_records=False without probes would "
                                 "record nothing; pass probes=...")
            key = (settings, n_ticks)
        else:
            from repro.obs.probes import resolve_probes
            specs = resolve_probes(self.program, probes)
            key = (settings, n_ticks, specs, keep_records)
        with jax.profiler.TraceAnnotation(
                "chip.run", n_ticks=n_ticks, cached=key in self._runs,
                exec_mode="event" if self.use_event_mode(exec_mode)
                else "dense",
                noc_mode="sparse" if self.use_sparse_noc(noc_mode)
                else "dense"):
            init, step, params = self.make_stepper(*settings)
            if key not in self._runs:
                with jax.profiler.TraceAnnotation("chip.compile"):
                    if probes:
                        entry = _probed_scan(step, params, init, n_ticks,
                                             specs, keep_records)
                    else:
                        entry = _scan(step, params, init, n_ticks)
                self._runs[key] = entry
            run, extra, finalize = self._runs[key]
            with jax.profiler.TraceAnnotation("chip.dispatch"):
                out = run(params, init, *extra)
        if not probes:
            return out
        (_, obs), recs = out
        recs = dict(recs) if keep_records else {}
        recs["probes"] = finalize(obs)
        return recs


def _scan(step, params, init, n_ticks: int) -> tuple:
    """``run``'s compiled scan, its extra arguments and finalizer."""
    def scan(params, init):
        return jax.lax.scan(lambda s, t: step(params, s, t),
                            init, jnp.arange(n_ticks))[1]
    return _compile(scan, params, init), (), None


def _probed_scan(step, params, init, n_ticks: int, specs: tuple,
                 keep_records: bool) -> tuple:
    """``run``'s compiled scan with probes, its extra arguments (the
    probe accumulators' initial state) and the probes' finalizer."""
    # telemetry: compile the probe accumulators into the scan carry
    # NEXT TO the workload state.  The probe step consumes the tick's
    # records and never feeds back into state, so probed runs stay
    # bit-identical to bare runs — only the carry grows.  (import here:
    # repro.obs reaches back into repro.chip for helpers)
    from repro.obs.probes import make_probe_step
    rec_shapes = jax.eval_shape(
        step, params, init, jax.ShapeDtypeStruct((), jnp.int32))[1]
    obs0, probe_step, finalize = make_probe_step(specs, rec_shapes, n_ticks)

    def probed_scan(params, init, obs0):
        def probed_tick(carry, t):
            state, obs = carry
            state, rec = step(params, state, t)
            obs = probe_step(obs, rec, t)
            return (state, obs), (rec if keep_records else {})
        return jax.lax.scan(probed_tick, (init, obs0), jnp.arange(n_ticks))

    return _compile(probed_scan, params, init, obs0), (obs0,), finalize


def _compile(fn: Callable, *args):
    """``jax.jit(fn)`` compiled ahead of time for ``args``, with the
    stage of every instruction recorded (``repro.obs.scopes``): a device
    op in a profiler trace names only its instruction."""
    from repro.obs.scopes import record
    compiled = jax.jit(fn).lower(*args).compile()
    record(compiled.as_text())
    return compiled


def chip_power_table(sim: ChipSim, recs: dict,
                     t_sys_s: float = 1e-3) -> dict:
    """Chip-level generalization of ``synfire_power_table``.

    per_pe     — the paper's Table III split (averaged over all PEs)
    chip       — the same, summed over the mesh [mW]
    noc        — average NoC power [mW], peak link load [packets/tick] and
                 [flits/tick], link utilization vs. capacity, worst
                 multicast hop depth
    """
    from repro.core.snn import synfire_power_table
    per_pe = synfire_power_table(recs, t_sys_s=t_sys_s)
    P = sim.program.n_pes
    chip = {mode: {k: v * P for k, v in per_pe[mode].items()}
            for mode in ("dvfs", "pl3")}

    loads = np.asarray(recs["link_load"])                  # (T, L)
    flits = np.asarray(recs.get("link_flits", loads))
    e_noc = np.asarray(recs["e_noc"])
    peak = float(sim.noc.congestion(loads).max()) if loads.size else 0.0
    peak_flits = float(sim.noc.congestion(flits).max()) if flits.size else 0.0
    cap = sim.noc.link_capacity_packets(t_sys_s, SPIKE_PACKET_BITS)
    # flit capacity: one flit per hop_cycles at the NoC clock
    cap_flits = t_sys_s * sim.noc.spec.freq_hz / sim.noc.spec.hop_cycles
    noc = {
        "power_mw": float(e_noc.mean() / t_sys_s * 1e3),
        "peak_link_load": peak,
        "mean_link_load": float(loads.mean()) if loads.size else 0.0,
        "peak_link_flits": peak_flits,
        "link_capacity": cap,                 # spike packets / tick
        "link_capacity_flits": cap_flits,     # basis of peak_utilization
        "peak_utilization": peak_flits / cap_flits,
        "worst_tree_hops": sim.program.worst_tree_hops,
        "worst_hop_latency_s": sim.noc.hop_latency_s(
            sim.program.worst_tree_hops),
        "n_links": sim.noc.n_links,
    }
    # tiered (board) NoC: split the accounting into on-chip vs
    # chip-to-chip shares — the headline number of the board benchmark
    if "flits_xchip" in recs:
        xmask = np.asarray(sim.noc.xlink_mask) > 0
        x_flits = float(np.asarray(recs["flits_xchip"]).sum())
        tot_flits = float(flits.sum())
        e_x = float(np.asarray(recs["e_noc_xchip"]).sum())
        e_tot = float(e_noc.sum())
        peak_x = (float(flits[:, xmask].max())
                  if xmask.any() and flits.size else 0.0)
        # the chip-to-chip tier has its own (slower) flit clock, so it
        # saturates long before its flit counts rival on-chip links
        xspec = sim.noc.xspec
        cap_x = t_sys_s * xspec.freq_hz / xspec.hop_cycles
        noc["xchip"] = {
            "n_links": int(xmask.sum()),
            "flits": x_flits,
            "flits_frac": x_flits / tot_flits if tot_flits else 0.0,
            "energy_frac": e_x / e_tot if e_tot else 0.0,
            "power_mw": float(np.asarray(recs["e_noc_xchip"]).mean()
                              / t_sys_s * 1e3),
            "peak_xlink_flits": peak_x,
            "link_capacity_flits": cap_x,
            "peak_utilization": peak_x / cap_x,
        }
        # tier-aware roll-ups: worst latency prices each tier at its own
        # hop cost (one real path's split — BoardProgram.path_hops), and
        # utilization is the worse of the two tiers' peaks vs their own
        # capacities (on-chip-only constants would understate the SerDes
        # tier by ~8x)
        peak_on = (float(flits[:, ~xmask].max())
                   if (~xmask).any() and flits.size else 0.0)
        noc["peak_utilization"] = max(peak_on / cap_flits, peak_x / cap_x)
        noc["worst_hop_latency_s"] = sim.program.worst_path_latency_s
    out = {"per_pe": per_pe, "chip": chip, "noc": noc,
           "n_pes": P, "mesh": (sim.program.mesh.width,
                                sim.program.mesh.height)}
    # on-mesh learning: e_learn share of the total chip energy (datapath
    # Eq. (1) terms + NoC traffic + learning) — the headline number of
    # the plasticity benchmark
    if "e_learn" in recs:
        e_l = np.asarray(recs["e_learn"])
        e_pe = sum(float(np.asarray(recs[k]).sum())
                   for k in ("e_dvfs_baseline", "e_dvfs_neuron",
                             "e_dvfs_synapse"))
        tot = e_pe + float(e_noc.sum()) + float(e_l.sum())
        out["learn"] = {
            "power_mw": float(e_l.sum(axis=-1).mean() / t_sys_s * 1e3),
            "energy_j": float(e_l.sum()),
            "energy_frac": float(e_l.sum()) / tot if tot else 0.0,
        }
    board = getattr(sim.program, "board", None)
    if board is not None:
        out["board"] = (board.chips_x, board.chips_y)
    return out
