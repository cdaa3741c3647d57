"""PCG solver: mean iterations per right-hand side in the window, from the
solver's own counter (``PCGResult.iterations``)."""


def read(m):
    if not m.iterations:
        return None
    return m.total_iterations / len(m.iterations)
