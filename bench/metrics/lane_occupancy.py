"""Share of decode lane-steps that emitted a token: generated tokens
over (decode steps x lanes), from the harness's counts of the traced
window."""


def read(x):
    w = x.window
    if w.steps == 0:
        return None
    return 100.0 * w.gen_tokens / (w.steps * x.lanes)
