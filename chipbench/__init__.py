"""Chip benchmark of the served VTA path: one cell per configuration and
traffic mix, named in ``BENCHMARK.json`` at the root of the repository, run
by ``chipbench/run.py``."""
