"""Device-idle milliseconds per chunked-prefill call: the idle time of
the traced window that falls under the engine's prefill spans (the
chunk loop, and per chunk its dispatch, its logits fetch and the host
argmax; ``engine_trace.py``), over the runs of the prefill program.
None where the program records no prefill span."""

from bench import engine_trace

PROGRAM = "prefill_step"
SPANS = ("engine.prefill", "engine.prefill.dispatch",
         "engine.prefill.fetch", "engine.prefill.argmax")


def read(x):
    _, n = x.summary.program_seconds(PROGRAM)
    es = engine_trace.of_run(x) if n else None
    idle = es.idle_under(SPANS) if es else None
    return None if idle is None else 1e3 * idle / n
