"""Device: the share of the traced window in which no operation ran on the
busiest device, in %."""


def read(m):
    if m.trace is None or m.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - m.trace.times.busy / m.trace.window_ns)
