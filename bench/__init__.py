"""The serving benchmark: harness (``run.py``), traffic generator,
trace reduction, operation and byte counts, weights, plain reference
and the comparison that decides ``correct``."""
