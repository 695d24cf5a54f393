"""Run one cell of the chip benchmark once.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The program under test is imported
from the checkout's ``src``.  The run exits non-zero, and prints no
result, when JAX's first device is not a TPU or when there are fewer
chips than the cell asks for.  Otherwise the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number the comparison with the plain reference
gave, beside its limit.  The checks are also the last lines of standard
error.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``,
so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from bench import device, spec  # noqa: E402


def prepare_environment() -> None:
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: no program under test at {src}/repro")
    sys.path.insert(0, str(src))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="python -m bench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def per_layer_values(cell: spec.Cell, window: dict) -> dict:
    """Each per-layer metric's reader over the window; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = spec.load_metric(m["name"]).read(window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: spec.Cell, res: dict, traced: bool) -> dict:
    checks = {name: {"value": res["checks"][name], "limit": limit}
              for name, limit in res["limits"].items()}
    correct = res["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    if traced:
        metrics = per_layer_values(cell, res["window"])
    else:
        metrics = {m["name"]: {"value": float(res["e2e"][m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    dev = dict(res["device"])
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": dev}
    tr = res["window"].get("trace")
    if traced and tr is not None:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    prepare_environment()
    cell = spec.load_cell(args.workload)
    try:
        devs = device.check_devices(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    res = spec.load_kind(cell.config).run(
        cell, args.seed, args.seconds, bool(args.trace), T_START, devs)
    line = result_line(cell, res, bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
