"""Device idle share of the traced check: 1 - the union of the device's
op intervals over the slice, averaged over the cell's devices
(``benchmark/trace.py``)."""

from benchmark import trace


def read(run: dict) -> float | None:
    if "trace" not in run:
        return None
    return trace.idle_share(run["trace"]["reduced"])
