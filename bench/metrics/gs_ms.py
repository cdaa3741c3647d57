"""Gather/scatter: device time per PCG iteration of the operations that read
or write an index map (Q gather, Q^T scatter-add, interface gather/set)."""


def read(m):
    return m.per_iteration_ms("gs")
