"""Table III + Fig. 17/18: synfire chain power with and without DVFS."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.configs import paper
from repro.core.snn import build_synfire, simulate_synfire, synfire_power_table


def main(n_ticks: int = 1200) -> None:
    net = build_synfire(0)
    t0 = time.perf_counter()
    recs = simulate_synfire(net, n_ticks)
    us = (time.perf_counter() - t0) / n_ticks * 1e6
    tab = synfire_power_table(recs)

    pl = np.asarray(recs["pl"])
    hist = np.bincount(pl.ravel(), minlength=3) / pl.size
    emit("fig18_pl_histogram", us,
         f"PL1={hist[0]:.3f};PL2={hist[1]:.3f};PL3={hist[2]:.3f}")

    spk = np.asarray(recs["spikes_exc"]).sum(axis=2)
    waves = np.where(spk[:, 0] > 100)[0]
    period = float(np.diff(waves[:6]).mean()) if len(waves) > 1 else -1
    emit("fig17_wave_period_ms", us, f"period={period};expected=80")

    for mode in ("pl3", "dvfs"):
        t = tab[mode]
        emit(f"tableIII_{mode}_mW", us,
             f"baseline={t['baseline']:.1f};neuron={t['neuron']:.2f};"
             f"synapse={t['synapse']:.2f};total={t['total']:.1f}")
    r = tab["reduction"]
    ref = paper.TABLE_III["reduction"]
    emit("tableIII_reduction", us,
         f"total={r['total']:.3f}(paper={ref['total']});"
         f"baseline={r['baseline']:.3f}(paper={ref['baseline']});"
         f"neuron={r['neuron']:.3f}(paper={ref['neuron']});"
         f"synapse={r['synapse']:.3f}(paper={ref['synapse']})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
