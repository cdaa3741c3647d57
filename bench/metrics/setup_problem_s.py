"""set-up: seconds in the program's ``setup.problem`` span
(``nekbone.setup_problem``), from its recorder."""


def read(m):
    rec = getattr(m, "recorder", None)
    if rec is None:
        return None
    spans = [s for s in rec.spans if s.name == "setup.problem"]
    return sum(s.seconds for s in spans) if spans else None
