"""Share of the traced window in which no operation ran on the device, as
``device_idle_share`` reads it (``bench/metrics/device_idle_share.py``)."""

from bench.run import load_reader

read = load_reader("device_idle_share")
