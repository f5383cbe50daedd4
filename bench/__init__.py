"""On-chip benchmark of the served co-occurrence path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix or metric is a file of its
own, found by its name: ``bench/configs/<config>.json``,
``bench/mixes/<traffic>.json`` and ``bench/metrics/<metric>.py``.
"""
