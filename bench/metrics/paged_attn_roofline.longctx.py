"""The paged decode kernel's share of its roofline, as
``paged_attn_roofline`` reads it
(``bench/metrics/paged_attn_roofline.py``)."""

from bench.run import load_reader

read = load_reader("paged_attn_roofline")
