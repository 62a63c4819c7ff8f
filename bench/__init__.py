"""Chip benchmark of the DiLoCo trainer and the serving engine.

``python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip and
prints one JSON result line. Everything that belongs to one model
configuration, traffic mix or per-layer metric is a file of its own
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found
by the name that ``BENCHMARK.json`` gives it.
"""
