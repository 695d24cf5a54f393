"""Fig. 20 + Fig. 21: NEF communication channel — decoded-output fidelity
and energy per (equivalent) synaptic event vs dimensions, against the
Loihi 24 pJ/synop reference point."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.configs import paper
from repro.core.nef import build_ensemble, run_channel, synop_metrics


def main(n_neurons: int = 512, ticks: int = 1200) -> None:
    # Fig. 21 plots energy/synop against population mean firing rate; we
    # sweep the drive amplitude to cover the rate axis (dims=1 column) and
    # sweep dims at fixed amplitude (the paper's dimensionality trend).
    for dims, amp in [(1, 0.4), (1, 0.8), (1, 1.4), (2, 0.8), (4, 0.8),
                      (8, 0.8), (16, 0.8)]:
        ens = build_ensemble(n_neurons, dims, seed=dims)
        t = np.arange(ticks)
        phases = np.linspace(0, np.pi, dims, endpoint=False)
        x = amp * np.sin(2 * np.pi * t[:, None] / 400 + phases[None, :]) \
            / np.sqrt(dims)
        t0 = time.perf_counter()
        out = run_channel(ens, x, use_mac=(dims == 1))
        us = (time.perf_counter() - t0) / ticks * 1e6
        rmse = float(np.sqrt(np.mean((out["xhat"][300:] - x[300:]) ** 2)))

        # dynamic energy per tick (the paper measures whole-core dynamic
        # power): N LIF updates on the Arm core (Table I e_neur), N*D MACs
        # on the array, D event-driven decode adds per spike
        mac_j_per_op = 1.0 / (paper.MAC_TOPS_PER_W[(0.50, 200e6)]
                              / paper.MAC_HW_BUG_FACTOR * 1e12)
        e_tick = (n_neurons * paper.NEF_E_NEURON_J
                  + 2.0 * n_neurons * dims * mac_j_per_op
                  + out["spikes_per_tick"] * dims * paper.PL2.e_synapse_j)
        m = synop_metrics(ens, out["spikes_per_tick"], e_tick)
        beats_loihi = m["pj_per_eq_synop"] < paper.LOIHI_PJ_PER_SYNOP
        emit(f"fig21_nef_D{dims}_amp{amp}", us,
             f"rmse={rmse:.3f};rate_hz={m['mean_rate_hz']:.1f};"
             f"pJ_eq_synop={m['pj_per_eq_synop']:.1f};"
             f"pJ_hw_synop={m['pj_per_hw_synop']:.1f};"
             f"loihi=24.0;beats_loihi={beats_loihi}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
