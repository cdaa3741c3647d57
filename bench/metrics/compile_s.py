"""set-up: seconds of tracing and compiling (cache loads included) that
the program's recorder counted before the window."""

from bench import scopes


def read(m):
    rec = getattr(m, "recorder", None)
    window = scopes.window_span(rec) if rec is not None else None
    if window is None:
        return None
    return scopes.compile_seconds(rec, window.start_ns)
