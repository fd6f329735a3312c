"""Share of the window's check time spent packing histories for the
ladder: the ``ladder.pack`` spans of ``parallel/batch.py``."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "ladder.pack"))
