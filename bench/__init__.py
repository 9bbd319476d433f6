"""The chip benchmark: one served cell per run, driven by data.

``BENCHMARK.json`` at the root of the repository names the cells; each
cell's configuration, traffic mix, server settings and per-layer metric
readers live in files of their own under this directory, found by name
(``spec.py``).  ``run.py`` is the entry point.
"""
