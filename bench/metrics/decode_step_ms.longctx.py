"""Device milliseconds per decode step, as ``decode_step_ms.chat`` reads it
(``bench/metrics/decode_step_ms.chat.py``)."""

from bench.run import load_reader

read = load_reader("decode_step_ms.chat")
