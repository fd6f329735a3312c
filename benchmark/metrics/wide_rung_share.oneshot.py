"""Share of the window's check time spent in launches of the ladder's
wide rungs (capacity 512 and up), compiles excepted: the
``ladder.launch`` spans of ``parallel/batch.py``, which end when the
launch's results are on the host.  0 where the window launched only
narrow rungs; nothing where it launched none."""

from benchmark.stats import in_window

WIDE = 512


def read(run: dict) -> float | None:
    launches = in_window(run, "ladder.launch")
    if not launches:
        return None
    wide = [s for s in launches if s["attrs"].get("capacity", 0) >= WIDE
            and not s["attrs"].get("compiled")]
    return 100.0 * sum(s["dur"] for s in wide) / run["checked_s"]
