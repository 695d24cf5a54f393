"""Discovery by name: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Each piece lives in a file of its own, found by its name:

* configuration ``<c>``: the ``file`` of its ``configs`` entry
  (``bench/configs/<c>.json``), which names the builder, the driver kind
  (``bench/kinds/<kind>.py``) and the plain reference
  (``bench/refs/<reference>.py``);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a module with
  ``read(window) -> float | None``.

Adding a configuration, a mix or a metric therefore adds files and
entries and edits none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set | None = None) -> bool:
    """Whether ``cell`` reports ``metric``: listed under its
    ``workloads``, or, without that key, everywhere its ``moves``
    metric (per-layer) or the metric itself (end-to-end) is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is None:
        return True
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def load_metric(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reader module ``bench/metrics/<name>.py`` (names may hold dots,
    so it is loaded by path, not imported by name)."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(config: dict) -> ModuleType:
    return importlib.import_module(f"bench.kinds.{config['kind']}")


def load_reference(config: dict) -> ModuleType:
    return importlib.import_module(f"bench.refs.{config['reference']}")


def resolve(path: str):
    """``"pkg.mod:attr"`` -> the attribute (the builder a config names)."""
    mod, _, attr = path.partition(":")
    obj = importlib.import_module(mod)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj
