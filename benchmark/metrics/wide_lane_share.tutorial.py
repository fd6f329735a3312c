"""Share of the traffic that the ladder's wide rungs (capacity 512 and
up) handle: the lanes that enter such a rung, summed over the window's
``ladder.stage`` spans of ``parallel/batch.py`` (attributes ``lanes``
and ``capacity``), over the keys the window checked.  A key that enters
two wide rungs counts at each.  0 where no lane left the narrow rungs;
nothing where the window staged no rung."""

from benchmark.stats import in_window

WIDE = 512


def read(run: dict) -> float | None:
    stages = in_window(run, "ladder.stage")
    if not stages or not run["verdicts"]:
        return None
    lanes = sum(s["attrs"].get("lanes", 0) for s in stages
                if s["attrs"].get("capacity", 0) >= WIDE)
    return 100.0 * lanes / len(run["verdicts"])
