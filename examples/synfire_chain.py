"""Synfire chain on 8 PEs with activity-driven DVFS (paper Sec. VI-B).

    PYTHONPATH=src python examples/synfire_chain.py [--ticks 400]

Prints an ASCII spike raster (exc populations), the PL timeline, and the
Table III power comparison.
"""
import argparse

import numpy as np

from repro.core.snn import build_synfire, simulate_synfire, synfire_power_table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=400)
    args = ap.parse_args()

    net = build_synfire(0)
    recs = simulate_synfire(net, args.ticks)
    spk = np.asarray(recs["spikes_exc"]).sum(axis=2)       # (T, P)
    pl = np.asarray(recs["pl"])                            # (T, P)

    print("spike raster (rows = PEs, cols = 4 ms bins; #: wave, .: sparse)")
    bins = spk[: args.ticks - args.ticks % 4].reshape(-1, 4, 8).sum(axis=1)
    for p in range(8):
        row = "".join("#" if b > 100 else ("." if b > 0 else " ")
                      for b in bins[:100, p])
        print(f"PE{p} |{row}|")

    print("\nPL timeline for PE0 (1=low power ... 3=peak):")
    print("".join(str(int(v) + 1) for v in pl[:100, 0]))

    tab = synfire_power_table(recs)
    print(f"\nonly-PL3: total {tab['pl3']['total']:.1f} mW   "
          f"DVFS: total {tab['dvfs']['total']:.1f} mW   "
          f"reduction {tab['reduction']['total']*100:.1f}% "
          f"(paper: 60.4%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
