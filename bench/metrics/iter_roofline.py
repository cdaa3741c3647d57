"""Device, whole iteration: the benchmark's count of one PCG iteration's
least time on one chip's share (``counts.iteration_cost`` against the peaks
table) over ``iter_ms``, in %."""

from bench import harness


def read(m):
    if m.trace is None or m.peak is None or m.total_iterations == 0:
        return None
    least_s, _ = harness.iteration_least(m)
    return 100.0 * least_s / (m.trace.window_ns / 1e9 / m.total_iterations)
