"""Device milliseconds per chunked-prefill call, as
``prefill_call_ms.chat`` reads it
(``bench/metrics/prefill_call_ms.chat.py``)."""

from bench.run import load_reader

read = load_reader("prefill_call_ms.chat")
