"""Shared benchmark utilities."""
from __future__ import annotations

import time

import jax

# every emit() also lands here so the driver can dump a machine-readable
# artifact (benchmarks/run.py --json)
RESULTS: list[dict] = []


def time_call(fn, *args, warmup=1, iters=3, **kw):
    """Median host wall time per call in microseconds, on whatever
    backend JAX runs (on the CPU, Pallas kernels run interpreted)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def _parse_derived(derived: str) -> dict:
    """'a=1;b=x' -> {'a': 1.0, 'b': 'x'} (numbers parsed where possible)."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
    RESULTS.append({"name": name, "us_per_call": round(us_per_call, 1),
                    "derived": derived, "values": _parse_derived(derived)})
