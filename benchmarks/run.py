"""Benchmark driver — one section per paper table/figure plus framework
benches.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only mac,synfire,...] \
        [--json artifacts/BENCH_latest.json]
"""
from __future__ import annotations

import argparse
import sys
import traceback

SECTIONS = [
    ("mac", "benchmarks.mac_efficiency", "Fig. 14/15 CoreMark + MAC TOPS/W"),
    ("synfire", "benchmarks.synfire", "Table III synfire DVFS power"),
    ("chip", "benchmarks.chip_scale", "chip-level mesh: power + link load"),
    ("nef", "benchmarks.nef_channel", "Fig. 20/21 NEF channel + pJ/synop"),
    ("dnn", "benchmarks.dnn_layers", "Fig. 22/23 DNN layer speedups"),
    ("lm", "benchmarks.lm_step", "framework LM step throughput"),
    ("roofline", "benchmarks.roofline_table", "dry-run roofline table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of sections: "
                    + ",".join(k for k, _, _ in SECTIONS))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON")
    args = ap.parse_args()
    want = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failed = []
    for key, mod_name, desc in SECTIONS:
        if want and key not in want:
            continue
        print(f"# --- {key}: {desc}", flush=True)
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failed.append(key)
            print(f"# {key} FAILED: {e}")
            traceback.print_exc()

    if args.json:
        from benchmarks.common import RESULTS
        from repro.obs import write_bench_json
        # the same manifest-stamped payload the scale benchmarks emit, so
        # every BENCH artifact is self-describing (git sha, versions,
        # host, timestamp)
        write_bench_json(args.json, RESULTS, failed_sections=failed,
                         config={"only": args.only})

    if failed:
        print(f"# sections failed: {failed}")
        sys.exit(1)
    print("# all sections complete")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
