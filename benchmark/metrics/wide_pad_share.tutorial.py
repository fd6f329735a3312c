"""Share of the lane slots that the ladder's wide-rung launches
(capacity 512 and up) spend on padding: the ``padded`` slots less the
live ``lanes``, over the ``padded`` slots, summed over the window's
``ladder.launch`` spans of ``parallel/batch.py``.  A padding slot
repeats the last live lane and does its work.  0 where the window
launched no wide rung; nothing where no launch carries ``padded`` (a
program that does not report the slots it launched)."""

from benchmark.stats import in_window

WIDE = 512


def read(run: dict) -> float | None:
    launches = [s for s in in_window(run, "ladder.launch")
                if "padded" in s["attrs"]]
    if not launches:
        return None
    wide = [s["attrs"] for s in launches if s["attrs"].get("capacity", 0) >= WIDE]
    slots = sum(a["padded"] for a in wide)
    if not slots:
        return 0.0
    return 100.0 * sum(a["padded"] - a["lanes"] for a in wide) / slots
