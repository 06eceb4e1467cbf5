"""The benchmark: cells, traffic, metrics and their reduction, kept apart
from the program under test (``kubeflow_tpu``).

``BENCHMARK.json`` at the root of the checkout is the manifest; everything
that belongs to one configuration, one traffic mix or one per-layer metric
is a file of its own under this package, found by the name in the manifest
(``benchmark/manifest.py``). ``python3 -m benchmark.run`` runs one cell.
"""
