"""A new configuration, traffic mix and per-layer metric are found by
name from files and entries alone."""
import json
import shutil
from pathlib import Path

from bench import run, spec

BENCH = Path(spec.BENCH_DIR)

METRIC = '''
def read(window):
    return window.get("jobs")
'''


def _tree(tmp_path: Path) -> tuple:
    root = tmp_path / "checkout"
    bench_dir = root / "bench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    return root, bench_dir


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root, bench_dir = _tree(tmp_path)
    cfg = json.loads((bench_dir / "configs" / "synfire-4096.json")
                     .read_text())
    cfg["name"] = "synfire-1024"
    cfg["args"]["n_pes"] = cfg["sizes"]["n_pes"] = 1024
    (bench_dir / "configs" / "synfire-1024.json").write_text(
        json.dumps(cfg))
    (bench_dir / "traffic" / "shot.json").write_text(json.dumps(
        {"ticks_per_job": 1000,
         "drive": {"noise_model": "shot", "kicks_per_tick": 4,
                   "kick": 0.5}}))
    (bench_dir / "metrics" / "jobs_in_window.sim.py").write_text(METRIC)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "synfire-1024", "source": "x",
                         "file": "bench/configs/synfire-1024.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "synfire1024-shot",
                           "config": "synfire-1024", "traffic": "shot",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "jobs_in_window.sim", "unit": "count",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine step (ChipSim tick)",
                           "moves": "tick_us",
                           "workloads": ["synfire1024-shot"]})
    b["end_to_end"][0]["workloads"].append("synfire1024-shot")
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("synfire1024-shot", root=root, bench_dir=bench_dir)
    assert cell.config["sizes"]["n_pes"] == 1024
    assert cell.traffic["drive"]["noise_model"] == "shot"
    assert [m["name"] for m in cell.end_to_end] == ["tick_us", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "jobs_in_window.sim" in names and "idle_share.sim" not in names
    reader = spec.load_metric("jobs_in_window.sim", bench_dir=bench_dir)
    assert reader.read({"jobs": 7}) == 7
    assert spec.load_kind(cell.config).__name__ == "bench.kinds.engine"
    # the existing cell is untouched by the additions
    old = spec.load_cell("synfire4096-gauss", root=root, bench_dir=bench_dir)
    assert "jobs_in_window.sim" not in [m["name"] for m in old.per_layer]


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cell = spec.load_cell("synfire4096-gauss")
    assert run.per_layer_values(cell, {"window_s": 1.0}) == {}


def test_every_metric_of_the_benchmark_has_its_files():
    b = spec.load_benchmark()
    for m in b["per_layer"]:
        assert hasattr(spec.load_metric(m["name"]), "read")
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert spec.load_kind(cell.config) and spec.load_reference(
            cell.config)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
