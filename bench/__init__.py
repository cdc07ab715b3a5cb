"""Chip benchmark of the replay fabric (see ``BENCHMARK.json`` and PERF.md).

Entered as ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Configurations, traffic mixes and per-layer metrics are
data and small readers found by name under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``.
"""
