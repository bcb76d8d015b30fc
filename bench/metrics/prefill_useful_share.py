"""Share of the prefill program's lane x token rows in the traced
window that held prompt tokens: the change of the engine's
``prefill_tokens`` counter over that of ``prefill_rows``, as the
window's admission sweeps carry them (``engine_trace.py``; every call
computes all lanes at the chunk width, padded to the longest prompt).
None where the program records no such counters or ran no prefill."""

from bench import engine_trace


def read(x):
    es = engine_trace.of_run(x)
    if es is None or "prefill_tokens" not in es.counts \
            or not es.counts.get("prefill_rows"):
        return None
    return 100.0 * es.counts["prefill_tokens"] / es.counts["prefill_rows"]
