"""Device: busy time of operations in no layer scope of the program, over
the busy time of the busiest device, in %."""


def read(m):
    scoped = getattr(m, "scoped", None)
    if scoped is None or scoped.times.busy <= 0:
        return None
    return 100.0 * scoped.times.by_class["unscoped"] / scoped.times.busy
