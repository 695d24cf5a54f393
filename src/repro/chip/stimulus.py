"""Where a network's drive comes from: one tick per network, two sources.

A stimulus window is ``{"r": (L,), "drive": (L, N)}``: the raw signal
and its s16.15 membrane drive, encoded through the ensemble's int8 MAC
array (``as_stim``).  ``encode_drive`` quantizes each time step on its
own (per-row int8 scales), so a window encoded in segments is
bit-identical to the same ticks encoded whole: a session that streams
its input one segment at a time drives the network exactly as the whole
signal preloaded would.

A semantics' tick asks its source for tick ``t`` of each of its K
channels (``reader``): the drive ``(K, N)`` and the signal ``(K,)``.

* ``TableStimulus`` is a table baked in at build time.  Channel k reads
  tick ``(t + k * phase_step) mod T``; co-prime steps decorrelate the
  channels' spike times.  It adds no state.
* ``StreamedStimulus`` is the window in ``state["stim"]``, read at
  ``t mod L`` by every channel.  The fleet engine swaps the window
  between scheduling rounds, and a checkpoint of the carry holds the
  in-flight input.  ``init_state`` preloads the default window, so a
  plain ``ChipSim.run`` of a streamed program needs nothing else.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.nef import encode_drive


def as_stim(r: np.ndarray, ens) -> dict:
    """A stimulus window: the raw signal and its MAC-encoded drive."""
    drive = np.asarray(encode_drive(ens, np.asarray(r, np.float32)[:, None],
                                    use_mac=True))
    return {"r": np.asarray(r, np.float32), "drive": drive}


def blank_stim(ens, n_ticks: int) -> dict:
    """The idle-slot stimulus: silence and its encoding."""
    return as_stim(np.zeros(n_ticks, np.float32), ens)


def _reader(tables, offsets) -> tuple:
    """``(drive_at, r_at)``, each ``(state, t) -> (K, ...)``: channel k
    reads tick ``(t + offsets[k]) mod L`` of ``tables(state) -> (drive
    (L, N), r (L,))``."""
    def drive_at(state, t):
        drive = tables(state)[0]
        return drive[(t + offsets) % drive.shape[0]]

    def r_at(state, t):
        r = tables(state)[1]
        return r[(t + offsets) % r.shape[0]]

    return drive_at, r_at


@dataclass
class TableStimulus:
    """A drive table and its signal, read ``k * phase_step`` ticks ahead
    by channel k."""
    drive: jnp.ndarray                  # (T, N) int32 s16.15
    r: np.ndarray                       # (T,) signal
    phase_step: int = 0

    @classmethod
    def sine(cls, ens, n_ticks: int, amplitude: float, period: float,
             phase_step: int = 0) -> "TableStimulus":
        """``n_ticks`` of a sine of ``amplitude`` and ``period`` ticks
        (the table wraps; a whole number of periods wraps without a
        jump)."""
        r = amplitude * np.sin(2 * np.pi * np.arange(n_ticks) / period)
        return cls(drive=encode_drive(ens, r[:, None], use_mac=True), r=r,
                   phase_step=phase_step)

    def init_state(self) -> dict:
        return {}

    def carry(self, state) -> dict:
        return {}

    def reader(self, n_channels: int) -> tuple:
        T = self.drive.shape[0]
        offsets = jnp.asarray((np.arange(n_channels) * self.phase_step) % T)
        tables = (self.drive, jnp.asarray(self.r, jnp.float32))
        return _reader(lambda state: tables, offsets)


@dataclass
class StreamedStimulus:
    """The window in ``state["stim"]``, the same for every channel;
    ``default`` sizes the window every streamed segment must match."""
    default: dict                       # {"r": (L,), "drive": (L, N)}

    def init_state(self) -> dict:
        return {"stim": {"r": jnp.asarray(self.default["r"]),
                         "drive": jnp.asarray(self.default["drive"])}}

    def carry(self, state) -> dict:
        return {"stim": state["stim"]}

    def reader(self, n_channels: int) -> tuple:
        # a gather at zero offsets, not a broadcast: the same read as the
        # table's keeps the records bitwise equal to a table at step 0
        return _reader(
            lambda state: (state["stim"]["drive"], state["stim"]["r"]),
            jnp.zeros(n_channels, jnp.int32))
