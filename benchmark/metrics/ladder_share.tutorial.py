"""Share of the window's check time spent in the ladder call: the
``ladder.run`` spans of ``checker/linearizable.py`` (as
``ladder_share.oneshot``, in the tutorial cell)."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "ladder.run"))
