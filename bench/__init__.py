"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/README.md`` for how cells, configurations, traffic mixes and
per-layer metrics are found by name.
"""
