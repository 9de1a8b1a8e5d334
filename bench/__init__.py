"""Chip benchmark of the POGO reproduction: harness, yardstick and references.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it is started on. Cells,
configurations, drivers and per-layer metric readers are found by name
under ``bench/workloads``, ``bench/configs``, ``bench/drivers`` and
``bench/metrics``.
"""
