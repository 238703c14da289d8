"""Chip benchmark for the funcX fabric: one cell is one model
configuration under one traffic mix, served through ``executor.submit``
to a TCP endpoint process that holds the chip.

Run one cell once (from the checkout root)::

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under this directory, found by the name
``BENCHMARK.json`` gives it.
"""
