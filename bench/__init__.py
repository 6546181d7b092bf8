"""The on-chip benchmark: one cell per run, driven by ``BENCHMARK.json``
(see ``bench/harness.py``)."""
