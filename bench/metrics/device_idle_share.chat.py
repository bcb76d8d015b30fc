"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(x):
    s = x.summary
    if s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
