"""Device milliseconds per chunked-prefill call: the device time of the
prefill program's runs in the trace, over their number."""

PROGRAM = "prefill_step"


def read(x):
    s, n = x.summary.program_seconds(PROGRAM)
    if n == 0:
        return None
    return 1e3 * s / n
