"""Span sums shared by the metric readers."""

from __future__ import annotations


def in_window(run: dict, name: str) -> list[dict]:
    """The run's spans called ``name`` that started inside its window."""
    t0, t1 = run["window"]
    return [s for s in run["spans"] if s["name"] == name and t0 <= s["t0"] <= t1]


def share(run: dict, spans: list[dict]) -> float | None:
    """The spans' seconds as a % of the time the window's checks took;
    None where the run has no span of the layer at all."""
    if not spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / run["checked_s"]
