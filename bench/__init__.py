"""The chip benchmark of the GCONV-chain engine; ``bench/run.py`` runs one
cell of ``BENCHMARK.json``."""
