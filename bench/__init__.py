"""On-chip serving benchmark, driven by ``BENCHMARK.json``.

Each configuration, traffic mix and metric is a file of its own under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by the
name ``BENCHMARK.json`` gives it.  ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` runs one cell.
"""
