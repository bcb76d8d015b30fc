"""The whole step's share of the chip's bf16 peak, as ``mfu`` reads it
(``bench/metrics/mfu.py``)."""

from bench.run import load_reader

read = load_reader("mfu")
