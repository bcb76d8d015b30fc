"""Device milliseconds per decode step: the device time of the fused
decode-loop program's runs in the trace, over the steps they ran (runs
times the decode block)."""

PROGRAM = "decode_loop"


def read(x):
    s, n = x.summary.program_seconds(PROGRAM)
    if n == 0:
        return None
    return 1e3 * s / (n * x.block)
