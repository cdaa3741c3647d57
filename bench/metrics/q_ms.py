"""gather/scatter: device time per PCG iteration in the program's ``gs.q``
scope (Q, global to element-local), on the busiest device."""

from bench import scopes


def read(m):
    return scopes.per_iteration_ms(m, "gs.q")
