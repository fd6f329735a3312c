"""Share of the window's check time that no span inside the check
explains: the self time of the window's ``checker.check`` spans, each
span's duration less the union of the other spans that start inside it
(cut at its end), over the time the checks took."""

from benchmark.stats import in_window
from benchmark.trace import union


def read(run: dict) -> float | None:
    checks = in_window(run, "checker.check")
    if not checks:
        return None
    own = 0.0
    for c in checks:
        a, b = c["t0"], c["t0"] + c["dur"]
        inside = [(s["t0"], min(b, s["t0"] + s["dur"])) for s in run["spans"]
                  if s is not c and a <= s["t0"] <= b]
        own += c["dur"] - sum(y - x for x, y in union(inside))
    return 100.0 * own / run["checked_s"]
