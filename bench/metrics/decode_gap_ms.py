"""Device-idle milliseconds per decode block: the idle time of the
traced window that falls under the engine's decode spans (the block
with its bookkeeping, the loop's dispatch and the fetch of its
outputs; a retirement or admission inside the block has spans of its
own; ``engine_trace.py``), over the runs of the fused decode-loop
program.  None where the program records no decode span."""

from bench import engine_trace

PROGRAM = "decode_loop"
SPANS = ("engine.step", "engine.decode.dispatch", "engine.decode.fetch")


def read(x):
    _, n = x.summary.program_seconds(PROGRAM)
    es = engine_trace.of_run(x) if n else None
    idle = es.idle_under(SPANS) if es else None
    return None if idle is None else 1e3 * idle / n
