"""PCG vector ops (dots, updates, mask, Jacobi, copies): the rest of the busy
device time per PCG iteration."""


def read(m):
    return m.per_iteration_ms("vec")
