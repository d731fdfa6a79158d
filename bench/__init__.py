"""On-chip benchmark of MINT serving: the harness that ``BENCHMARK.json``
at the root of the repository describes. ``bench/run.py`` runs one cell."""
