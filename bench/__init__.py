"""On-chip benchmark of the FlexRound PTQ -> export -> serve system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on. Everything a cell needs is found by name: configurations in
``bench/configs``, traffic mixes in ``bench/traffic``, per-layer metric
readers in ``bench/metrics``.
"""
