"""Share of the keys checked that the device ladder left undecided, its
widest rung lossy, and handed whole to the exact sweep in the
confirmation workers: the ``undecided`` attribute of the window's
``ladder.confirm.drain`` spans of ``parallel/batch.py``, over the keys
the window checked.  Nothing where no drain span carries the attribute
(a program that sends such keys to its in-process CPU fallback)."""

from benchmark.stats import in_window


def read(run: dict) -> float | None:
    drains = [s for s in in_window(run, "ladder.confirm.drain")
              if "undecided" in s["attrs"]]
    if not drains or not run["verdicts"]:
        return None
    return 100.0 * sum(s["attrs"]["undecided"] for s in drains) / len(run["verdicts"])
