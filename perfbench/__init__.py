"""On-chip serving benchmark: the yardstick kept apart from the program.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a cell needs is found by
name: its configuration under ``configs/``, its traffic mix under
``traffic/``, its tier and correctness limits under ``cells/``, and one
reader per metric under ``metrics/``.
"""
