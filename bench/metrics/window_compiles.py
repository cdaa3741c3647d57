"""PCG solver: traces, compiles and cache loads that the program's recorder
counted inside the window (none is expected)."""

from bench import scopes


def read(m):
    rec = getattr(m, "recorder", None)
    window = scopes.window_span(rec) if rec is not None else None
    if window is None:
        return None
    return scopes.window_compiles(rec, window.start_ns, window.end_ns)
