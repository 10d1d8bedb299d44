"""The chip benchmark: census rounds of one cell, timed on one TPU.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
from the checkout root. Everything that belongs to one configuration, traffic
mix, family or per-layer metric is a file of its own under this directory,
found by the name ``BENCHMARK.json`` gives it.
"""
