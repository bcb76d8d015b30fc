"""Share of decode lane-steps that emitted a token, as ``lane_occupancy``
reads it (``bench/metrics/lane_occupancy.py``)."""

from bench.run import load_reader

read = load_reader("lane_occupancy")
