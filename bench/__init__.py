"""On-chip benchmark of the SPARe trainer and serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once. Everything is found by name: cells in
``bench/workloads/``, model configurations in ``bench/configs/``, traffic
mixes in ``bench/traffic/``, metric readers in ``bench/metrics/``.
"""
