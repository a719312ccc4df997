"""device_idle_pct: share of the traced window in which no operation ran on
the device, averaged over the cell's chips."""


def read(w):
    if w.trace is None or w.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
