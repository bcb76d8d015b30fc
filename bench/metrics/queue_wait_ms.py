"""Mean milliseconds a request admitted in the traced window waited in
the engine's admission queue: the change of the engine's
``queue_wait_s`` counter over the window, over the change of
``admitted``, as the window's admission sweeps carry them
(``engine_trace.py``).  None where the program records no such counter
or admitted nothing."""

from bench import engine_trace


def read(x):
    es = engine_trace.of_run(x)
    if es is None or "queue_wait_s" not in es.counts \
            or not es.counts.get("admitted"):
        return None
    return 1e3 * es.counts["queue_wait_s"] / es.counts["admitted"]
