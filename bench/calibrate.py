"""Readings of a cell's control, for setting the limits of its checks.

    python -m bench.calibrate --workload <cell> --seconds <s> --seeds <n> ...

For each seed, puts the configuration's plain reference, computed in
the next lower precision (bfloat16), in the program's place at the
cell's own size, and prints the numbers the comparison gives: the upper
readings of the limits.  The lower readings are the checks that the
benchmark's own runs print.  Like ``bench.run``, it refuses to run off
the chip.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import device, spec
from bench.run import prepare_environment


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m bench.calibrate")
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    prepare_environment()
    cell = spec.load_cell(args.workload)
    try:
        device.check_devices(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    kind = spec.load_kind(cell.config)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": kind.control(cell, seed, args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
