"""Share of the window's check time spent splitting each keyed history
into its per-key subhistories: the ``independent.split`` spans of
``independent.py`` (the key scan, every subhistory and its index)."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "independent.split"))
