"""On-chip benchmark of the serving and training paths.

``bench/cell.py`` runs one cell of ``BENCHMARK.json``; everything that
belongs to one configuration, traffic mix, metric or cell is a file of
its own under ``configs/``, ``traffic/``, ``metrics/`` and ``limits/``,
found by the name the manifest gives it (see ``bench/spec.py``).
"""
