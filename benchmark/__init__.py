"""The benchmark of veles_tpu (BENCHMARK.json at the root of the checkout).

Everything the yardstick is made of lives here: the runner (`run.py`),
the manifest loader, the drivers, the generators of weights and inputs,
the plain reference and the comparison that decides `correct`, the
operation counts, the table of peaks and the trace reduction. From the
program it takes only the system under test. See README.md.
"""
