"""Share of the window's check time spent waiting, after the ladder, for
the confirmation workers: the ``ladder.confirm.drain`` spans of
``parallel/batch.py`` (as ``confirm_tail_share.oneshot``, in the
tutorial cell)."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "ladder.confirm.drain"))
