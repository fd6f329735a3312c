"""Share of the window's check time spent in the ladder call: the
``ladder.run`` spans of ``checker/linearizable.py``, around the whole of
``batch_analysis`` (pack, launches, confirmation drain and the host work
between them)."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "ladder.run"))
