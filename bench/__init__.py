"""Chip benchmark of the decentralized kPCA fit and projection serving.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it finds.
Configurations, traffic mixes, drivers, arrival processes, row
distributions and per-layer metrics are found by name, each a file of its
own (see ``bench/run.py`` and ``bench/traffic.py``).
"""
