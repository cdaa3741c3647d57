"""gather/scatter: device time per PCG iteration in the program's
``gs.iface`` scope (the sharded exchange's interface gather and set), on
the busiest device; only where the cell runs on more than one chip."""

from bench import scopes


def read(m):
    if m.chips < 2:
        return None
    return scopes.per_iteration_ms(m, "gs.iface")
