"""``bench.run`` refuses to measure off the chip."""
import json
import os
import subprocess
import sys

from bench import spec


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "bench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_with_no_result_off_the_tpu():
    p = _run(["--workload", "synfire4096-gauss", "--seed", "3000000000",
              "--seconds", "1", "--trace", "0"], spec.ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            assert "metrics" not in json.loads(line)
        except json.JSONDecodeError:
            pass


def test_exits_nonzero_without_the_program(tmp_path):
    import shutil
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench")
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(["--workload", "synfire4096-gauss", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
