"""Where the compiled engine put each named stage of its tick.

``ChipSim``'s tick runs every stage under a ``jax.named_scope`` whose
name is in ``STAGES``: ``chip_tick`` holds ``semantics`` (the workload's
tick), ``learn`` (plastic programs only) and ``noc`` (with ``xchip``, the
chip-to-chip tier of a board); the synfire tick
(``repro.core.snn.make_synfire_tick``) splits ``semantics`` into
``fifo``, ``synapse`` (the event tick's cond branches are ``compressed``
and ``dense_fallback``), ``background``, ``neuron`` and ``route``, and
the hybrid farm's tick (``repro.chip.workloads.HybridFarmSemantics``)
into ``background``, ``neuron``, ``synapse`` and ``route``.

XLA keeps the scopes in each instruction's ``op_name`` metadata, but a
device op in a profiler trace read through ``jax.profiler.ProfileData``
carries only its instruction.  So ``ChipSim.run`` compiles ahead of time
and passes each compiled program's HLO text to ``record``; ``table()``
then maps an HLO module name and an instruction name to the
instruction's stage path, e.g. ``chip_tick/semantics/synapse/
dense_fallback``, for a trace reduction to look device ops up in.  A
module compiled again under the same name replaces its entry.
"""
from __future__ import annotations

import re

STAGES = frozenset({"chip_tick", "semantics", "learn", "noc", "xchip",
                    "fifo", "synapse", "compressed", "dense_fallback",
                    "background", "neuron", "route"})
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_OP = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = [^\n]*?op_name="([^"]*)"',
                 re.M)
# one table per process: the trace reduction that reads it is handed no
# ChipSim, only the trace
_TABLE: dict = {}


def stage_path(op_name: str) -> str:
    """The stage names in an ``op_name``, outermost first, "/"-joined
    ("" when the op ran under no stage)."""
    return "/".join(p for p in op_name.split("/") if p in STAGES)


def record(hlo_text: str) -> None:
    """Keep the stage path of every staged instruction of one compiled
    module (its optimized HLO text)."""
    m = _MODULE.search(hlo_text)
    if m is None:
        return
    ops = {}
    for name, op_name in _OP.findall(hlo_text):
        path = stage_path(op_name)
        if path:
            ops[name] = path
    _TABLE[m.group(1)] = ops


def table() -> dict:
    """``{module: {instruction: stage path}}`` of the programs compiled
    so far in this process."""
    return {module: dict(ops) for module, ops in _TABLE.items()}
