"""In-scan telemetry: probes, Perfetto traces, run manifests, bench diffs.

The real SpiNNaker 2 PE drives DVFS from live activity counters — per-PE
performance monitoring is an architectural feature, not an afterthought
(Mayr et al., arXiv:1911.02385).  This package is the simulator's
equivalent, in these layers:

* ``probes``   — declarative ``ProbeSpec``s compiled INTO the engine's
  ``lax.scan`` carry: sampling strides + windowed reductions (peak /
  mean / EMA / last) so board-scale runs record without host round-trips
  or per-tick memory blow-up.  Zero probes trace bitwise-identically to
  the bare engine.
* ``scopes``   — the named stages of the engine's tick as the compiled
  program placed them, for splitting a device profile by stage.
* ``trace``    — export of recorded timelines to Chrome/Perfetto
  trace-event JSON (per-PE compute/DVFS tracks, per-NoC-tier flit
  counters, learn updates), viewable at https://ui.perfetto.dev.
* ``manifest`` — a provenance manifest (git sha, config hash, seed,
  jax/jaxlib versions, host) + host-side phase timers attached to every
  BENCH json artifact.
* ``report``   — ``python -m repro.obs.report A.json B.json`` diffs two
  BENCH artifacts and exits nonzero past a regression threshold (the CI
  regression gate).
"""
from repro.obs.health import (SloMonitor, SloRule, default_fleet_slos,
                              parse_slo)
from repro.obs.manifest import (PhaseTimers, bench_payload, config_hash,
                                run_manifest, write_bench_json)
from repro.obs.metrics import (Counter, DeviceMetricSpec, Gauge, Histogram,
                               MetricsRegistry, device_metrics_for,
                               make_device_metrics)
from repro.obs.probes import (PROBE_REGISTRY, ProbeSpec, default_probes,
                              link_profile, link_profile_probes,
                              record_link_profile, resolve_probes)
from repro.obs.spans import (SpanEvent, SpanLog, load_spans,
                             validate_spans)

__all__ = [
    "Counter", "DeviceMetricSpec", "Gauge", "Histogram",
    "MetricsRegistry", "PROBE_REGISTRY", "PhaseTimers", "ProbeSpec",
    "SloMonitor", "SloRule", "SpanEvent", "SpanLog", "bench_payload",
    "config_hash", "default_fleet_slos", "default_probes",
    "device_metrics_for", "diff_benches", "fleet_trace_events",
    "link_profile", "link_profile_probes", "load_spans",
    "make_device_metrics", "parse_slo", "record_link_profile",
    "resolve_probes", "run_manifest", "trace_events", "validate_spans",
    "write_bench_json", "write_fleet_trace", "write_trace",
]

_LAZY = {"diff_benches": "repro.obs.report",
         "trace_events": "repro.obs.trace",
         "fleet_trace_events": "repro.obs.trace",
         "write_fleet_trace": "repro.obs.trace",
         "write_trace": "repro.obs.trace"}


def __getattr__(name):
    # report/trace are also ``python -m`` entry points; importing them
    # eagerly here would trip runpy's double-import warning, so their
    # re-exports resolve on first use instead
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
