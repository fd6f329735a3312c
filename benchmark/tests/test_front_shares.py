"""The readers of the check's front: ``split_share``, ``ladder_share``,
``evidence_share`` and ``front_self_share``, on runs made up here, and
on one traced run of the cell at a small size on the CPU."""

from __future__ import annotations

import pytest

from benchmark import run
from benchmark.tests import small

NAMES = ("split_share.oneshot", "ladder_share.oneshot",
         "evidence_share.oneshot", "front_self_share.oneshot")


def reader(name: str):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           f"test_reader_{name.replace('.', '_')}")


def span(name: str, t0: float, t1: float, **attrs) -> dict:
    return {"name": name, "t0": t0, "dur": t1 - t0, "attrs": attrs}


def made_up_run() -> dict:
    """Two 5 s checks in a window of 10-30 s, and one after it.

    The first: split 1 s, ladder 3 s with a pack and a launch nested in
    it and a span that overlaps both the ladder and the evidence,
    evidence 0.5 s, save 0.1 s, so 0.4 s of it is its own.  The second:
    split 2 s, then a span that starts inside it and ends after it, so
    its own time is 2 s."""
    spans = [
        span("checker.check", 10.0, 15.0),
        span("independent.split", 10.0, 11.0),
        span("ladder.run", 11.0, 14.0),
        span("ladder.pack", 11.0, 11.1),
        span("ladder.launch", 11.5, 12.0, capacity=64),
        span("other", 13.5, 14.2),
        span("checker.evidence", 14.0, 14.5),
        span("independent.save", 14.5, 14.6),
        span("checker.check", 20.0, 25.0),
        span("independent.split", 20.0, 22.0),
        span("ladder.run", 24.0, 26.0),
        span("checker.check", 40.0, 45.0),
        span("independent.split", 40.0, 44.0),
    ]
    return {"window": (10.0, 30.0), "checked_s": 10.0, "spans": spans}


def test_span_shares():
    r = made_up_run()
    assert reader("split_share.oneshot").read(r) == pytest.approx(30.0)
    assert reader("ladder_share.oneshot").read(r) == pytest.approx(50.0)
    assert reader("evidence_share.oneshot").read(r) == pytest.approx(5.0)


def test_front_self_share_nested_and_overlapping_children():
    # 0.4 s of the first check and 2 s of the second: the nested pack
    # and launch and the overlapping span count once, and the span that
    # outlives the second check counts up to its end
    got = reader("front_self_share.oneshot").read(made_up_run())
    assert got == pytest.approx(100.0 * 2.4 / 10.0)


def test_front_self_share_leaves_out_spans_outside_the_check():
    r = made_up_run()
    r["spans"].append(span("independent.split", 9.0, 10.5))  # starts before
    r["spans"].append(span("elsewhere", 17.0, 18.0))  # between the checks
    got = reader("front_self_share.oneshot").read(r)
    assert got == pytest.approx(100.0 * 2.4 / 10.0)


def test_front_self_share_no_children_is_the_whole_check():
    r = {"window": (0.0, 10.0), "checked_s": 4.0,
         "spans": [span("checker.check", 1.0, 5.0)]}
    assert reader("front_self_share.oneshot").read(r) == pytest.approx(100.0)


def test_a_child_a_microsecond_past_the_end_leaves_no_sliver():
    # spans are recorded to the microsecond: a child can end a rounding
    # step after its parent
    r = {"window": (0.0, 10.0), "checked_s": 1.0,
         "spans": [span("checker.check", 1.0, 2.0),
                   span("independent.split", 1.0, 2.000001)]}
    assert reader("front_self_share.oneshot").read(r) == pytest.approx(
        0.0, abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_gives_none(name):
    # a program without the span (the parent commit) reads as no metric
    r = {"window": (0.0, 10.0), "checked_s": 1.0,
         "spans": [span("ladder.pack", 1.0, 2.0)]}
    assert reader(name).read(r) is None


def test_traced_small_run_reports_the_front():
    """A traced run of the cell, small and on the CPU: the four shares
    are in the result line, correct, and together they hold the check
    (the per-key saves, the one span under the check that no share
    reads, take well under 1 %)."""
    args = run.parse_args(["--workload", "defaults-oneshot", "--seed",
                           "2147490011", "--seconds", "1", "--trace", "1"])
    line = run.run_cell(args, small.spec(), allow_cpu=True,
                        overrides=small.SMALL["defaults-oneshot"])
    assert line["correct"]
    got = {n: line["metrics"][n]["value"] for n in NAMES}
    assert all(v >= 0 for v in got.values())
    assert 95.0 <= sum(got.values()) <= 101.0
    assert got["front_self_share.oneshot"] < 5.0
