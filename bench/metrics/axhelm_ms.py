"""axhelm kernel: device time per PCG iteration of the Pallas kernel's
events, found by the kernel's name."""


def read(m):
    return m.per_iteration_ms("axhelm")
