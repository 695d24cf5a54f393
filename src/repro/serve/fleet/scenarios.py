"""Served workloads: session streams and responses over the benchmarked
semantics.

The two scenarios Yan et al. (arXiv:2009.08921) frame as one user per
instance:

* **adaptive control**: each session is a closed PES-learning control
  loop (``repro.learn.adaptive``).  The session streams its reference
  r(t) in; the instance tracks it through the mesh and streams the plant
  state and the tracking error out.  Decoders adapt per session, so two
  users' instances end up with different weights.
* **keyword spotting (KWS)**: each session streams an audio-like
  waveform (one of ``n_keywords`` synthetic keyword templates) into the
  hybrid NEF -> event-MAC farm (``repro.chip.workloads``).  The instance
  streams the MAC layer's hidden activations out, and the response sums
  them into a per-request score vector.

A scenario adds no tick of its own.  It builds the network's one graph
with a ``StreamedStimulus`` (``repro.chip.stimulus``) in place of the
baked drive table, so every channel reads the session's window from
``state["stim"]``.  What it does define is the session side: how to open
a session's input stream (``SineStream``), which records stream back to
the user, and how a finished session's outputs become its response.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from repro.chip.graph import NetGraph
from repro.chip.stimulus import StreamedStimulus, as_stim, blank_stim
from repro.chip.workloads import hybrid_farm_graph
from repro.core.nef import build_ensemble
from repro.learn.adaptive import adaptive_control_graph


# -------------------------------------------------------------------------
# Session input streams
# -------------------------------------------------------------------------

@dataclass
class SineStream:
    """One user's input stream: an amp/period/phase sine drawn from the
    session seed (the Yan-et-al. stimulus class, one parameterization per
    user).  ``segment(t0, n)`` returns ticks [t0, t0+n) of the stream as
    a stimulus window — deterministic in (seed, t0, n), so a preempted
    session regenerates exactly the input it would have seen."""
    ens: object
    seed: int
    keyword: Optional[int] = None         # KWS: index into the period table
    periods: tuple = (64.0, 96.0, 144.0, 216.0)
    # control references are SLOW sines (the Yan-et-al. stimulus class —
    # trackable through the loop's 2-tick transport delay); keyword
    # waveforms are fast enough to separate spike patterns per class
    period_range: tuple = (512.0, 2048.0)

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        if self.keyword is None:
            self.amp = float(rng.uniform(0.3, 0.9))
            self.period = float(rng.uniform(*self.period_range))
        else:                              # keyword template + user timbre
            self.amp = float(rng.uniform(0.6, 0.9))
            self.period = float(self.periods[self.keyword
                                             % len(self.periods)])
        self.phase = float(rng.uniform(0.0, self.period))

    def signal(self, t0: int, n: int) -> np.ndarray:
        t = np.arange(t0, t0 + n, dtype=np.float64)
        return (self.amp * np.sin(2 * np.pi * (t + self.phase)
                                  / self.period)).astype(np.float32)

    def segment(self, t0: int, n: int) -> dict:
        return as_stim(self.signal(t0, n), self.ens)


# -------------------------------------------------------------------------
# The scenario catalog the fleet engine serves from
# -------------------------------------------------------------------------

@dataclass
class ServedScenario:
    """Everything the fleet engine needs to serve one workload class:
    how to build the program for a given stimulus window, how to open a
    session's input stream, which per-tick rec keys stream back to the
    user, and how to summarise a finished session into a response."""
    name: str
    ens: object
    build_graph: Callable                 # (stim) -> NetGraph
    make_stream: Callable                 # (seed) -> SineStream
    output_keys: tuple
    response: Callable = None             # ({key: (T, ...) np}) -> dict

    def graph(self, stim_len: int, stim: dict | None = None) -> NetGraph:
        return self.build_graph(stim if stim is not None
                                else blank_stim(self.ens, stim_len))

    def stream(self, seed: int):
        return self.make_stream(seed)


def adaptive_scenario(n_channels: int = 1, n_neurons: int = 64,
                      seed: int = 0, learning_rate: float = 3e-6,
                      plastic: bool = True) -> ServedScenario:
    """Adaptive-control-as-a-service: per-session PES learning."""
    ens = build_ensemble(n_neurons, 1, seed=seed)

    def build(stim):
        return adaptive_control_graph(n_channels, n_neurons, seed=seed,
                                      learning_rate=learning_rate,
                                      plastic=plastic,
                                      stimulus=StreamedStimulus(stim))

    def response(outs: dict) -> dict:
        err = np.asarray(outs["track_err"])         # (T, K)
        tail = max(1, len(err) // 4)
        return {"final_err": float(err[-tail:].max(axis=1).mean()),
                "initial_err": float(err[:tail].max(axis=1).mean())}

    return ServedScenario(
        name=f"adaptive{n_channels}ch", ens=ens, build_graph=build,
        make_stream=lambda seed: SineStream(ens, seed),
        output_keys=("u", "y", "r", "track_err"), response=response)


def kws_scenario(n_pairs: int = 1, n_neurons: int = 64, hidden: int = 16,
                 n_keywords: int = 4, seed: int = 0) -> ServedScenario:
    """Keyword spotting on the hybrid farm: each session streams one of
    ``n_keywords`` waveform templates; the response is the time-mean
    hidden-activation profile (the per-request score vector)."""
    ens = build_ensemble(n_neurons, 1, seed=seed)

    def build(stim):
        return hybrid_farm_graph(n_pairs, n_neurons, hidden, seed=seed,
                                 stimulus=StreamedStimulus(stim))

    def make_stream(session_seed: int):
        kw = int(np.random.default_rng(session_seed).integers(n_keywords))
        return SineStream(ens, session_seed, keyword=kw)

    def response(outs: dict) -> dict:
        h = np.asarray(outs["hidden_out"])          # (T, K, hidden)
        scores = np.abs(h).mean(axis=(0, 1))        # (hidden,)
        return {"scores": scores.round(5).tolist(),
                "top_unit": int(scores.argmax()),
                "spikes": float(np.asarray(outs["n_spk"]).sum())}

    return ServedScenario(
        name=f"kws{n_pairs}ch", ens=ens, build_graph=build,
        make_stream=make_stream, output_keys=("hidden_out", "n_spk"),
        response=response)


SCENARIOS = {"adaptive": adaptive_scenario, "kws": kws_scenario}
