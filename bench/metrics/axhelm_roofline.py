"""axhelm kernel: the paper's least time of one apply on one chip's elements
(Tables 3-4 at the cell's word size, against the peaks table) over the
kernel's device time per apply, in %.  Nothing to read without kernel
events."""

from bench import harness


def read(m):
    if m.trace is None or m.peak is None or m.applies == 0:
        return None
    kernel_ns = m.trace.times.by_class["axhelm"]
    if kernel_ns <= 0:
        return None
    least_s, _ = harness.axhelm_least(m)
    return 100.0 * least_s / (kernel_ns / 1e9 / m.applies)
