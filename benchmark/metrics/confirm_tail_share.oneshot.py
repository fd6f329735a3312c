"""Share of the window's check time spent waiting, after the ladder, for
the CPU workers that confirm refutations: the ``ladder.confirm.drain``
spans of ``parallel/batch.py``."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "ladder.confirm.drain"))
