"""The device a run measures: what JAX reports, the peak table, seeds."""
from __future__ import annotations

import json

import numpy as np

from bench.spec import BENCH_DIR


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise.  A
    measurement never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     "TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def describe(devs: list) -> dict:
    """``device`` of the result line; ``memory_peak_bytes`` is the peak of
    the fullest chip, as the backend reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if peaks:
        out["memory_peak_bytes"] = max(peaks)
    return out


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    ``bench/peaks.json`` is an error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def sub_seeds(seed: int, n: int) -> list[int]:
    """``n`` independent 31-bit seeds drawn from ``--seed`` (any size)."""
    words = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in words]
