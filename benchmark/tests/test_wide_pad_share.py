"""``wide_pad_share.tutorial``'s reader on runs made up here."""

from __future__ import annotations

import pytest

from benchmark import run


def reader():
    return run.load_module(run.BENCH / "metrics" / "wide_pad_share.tutorial.py",
                           "test_reader_wide_pad_share")


def launch(t0: float, capacity: int, lanes: int, padded: int | None = None) -> dict:
    attrs = {"capacity": capacity, "lanes": lanes}
    if padded is not None:
        attrs["padded"] = padded
    return {"name": "ladder.launch", "t0": t0, "dur": 0.1, "attrs": attrs}


def made_up_run(spans: list[dict]) -> dict:
    return {"window": (10.0, 30.0), "checked_s": 5.0, "spans": spans}


def test_reads_the_padding_slots_of_wide_launches():
    # wide slots: 8 + 4 + 1 = 13, of them padding 7 + 1 + 0 = 8; the
    # narrow launch and the launch before the window count for nothing
    r = made_up_run([
        launch(11.0, 64, 2, 8),
        launch(12.0, 512, 1, 8),
        launch(13.0, 4096, 3, 4),
        launch(14.0, 4096, 1, 1),
        launch(5.0, 4096, 1, 8),
    ])
    assert reader().read(r) == pytest.approx(100.0 * 8 / 13)


def test_no_wide_launch_reads_zero():
    r = made_up_run([launch(11.0, 64, 2, 8), launch(12.0, 1, 100, 128)])
    assert reader().read(r) == 0.0


@pytest.mark.parametrize("spans", [
    [],
    [launch(11.0, 512, 1), launch(12.0, 4096, 3)],
    [launch(5.0, 4096, 1, 8)],
], ids=["no launch", "no padded attribute", "only before the window"])
def test_nothing_to_read_gives_none(spans):
    # a program whose launches do not report their slots (the parent
    # commit) reads as no metric
    assert reader().read(made_up_run(spans)) is None
