"""PCG solver: the traced window over all PCG iterations in it, in ms."""


def read(m):
    if m.trace is None or m.total_iterations == 0:
        return None
    return m.trace.window_ns / m.total_iterations / 1e6
