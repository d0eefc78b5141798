"""Chip benchmark of the federated mesh round (``python3 bench/run.py``).

Everything one configuration, traffic mix, protocol or per-layer metric needs
sits in a file of its own here and is found by the name ``BENCHMARK.json``
gives it; see ``run.py``.
"""
