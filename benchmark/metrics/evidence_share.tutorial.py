"""Share of the window's check time spent on the per-key evidence
bundles: the ``checker.evidence`` spans of ``checker/linearizable.py``
(as ``evidence_share.oneshot``, in the tutorial cell)."""

from benchmark.stats import in_window, share


def read(run: dict) -> float | None:
    return share(run, in_window(run, "checker.evidence"))
