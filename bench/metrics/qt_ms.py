"""gather/scatter: device time per PCG iteration in the program's ``gs.qt``
scope (Q^T, the element-local sum into global dofs), on the busiest
device."""

from bench import scopes


def read(m):
    return scopes.per_iteration_ms(m, "gs.qt")
