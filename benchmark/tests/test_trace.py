"""The trace reduction on a small trace recorded on the chip.

``benchmark/recorded/v5e_slice.xplane.pb`` was recorded by
``benchmark/recorded/record.py`` on a TPU v5e: between two slice marks,
five launches of one matmul chain with 50 ms sleeps around them.  The
reduction must find five bursts of busy time that add up to what the
device's own module line says, six idle gaps of about 50 ms, and the
numbers kept beside the trace."""

import json
from pathlib import Path

import pytest

from benchmark import trace as bt

REC = Path(__file__).resolve().parents[1] / "recorded"


@pytest.fixture(scope="module")
def red():
    pd = bt.load(REC / "v5e_slice.xplane.pb")
    return bt.reduce(pd, devices=[0])


@pytest.fixture(scope="module")
def expected():
    return json.loads((REC / "v5e_slice.expected.json").read_text())


def test_numbers_as_kept(red, expected):
    assert red["slice_s"] == pytest.approx(expected["slice_s"], abs=1e-12)
    assert bt.busy_s(red) == pytest.approx(expected["busy_s"], abs=1e-12)
    assert bt.idle_share(red) == pytest.approx(expected["idle_share"], abs=1e-9)
    gaps = [(b - a) / 1e9 for a, b in red["devices"][0]["gaps"] if b - a > 1e6]
    assert gaps == pytest.approx(expected["gaps_over_1ms_s"], abs=1e-12)
    assert bt.top_ops(red, 3) == [[n, pytest.approx(s, abs=1e-12)]
                                  for n, s in expected["top_ops"]]


def test_busy_agrees_with_the_module_line(expected):
    # the ops of a module run back to back: their union is the module time
    assert expected["modules"] == 5
    assert expected["busy_s"] == pytest.approx(expected["module_seconds"],
                                               rel=0.01)


def test_gaps_are_the_sleeps(expected):
    gaps = expected["gaps_over_1ms_s"]
    assert len(gaps) == 6
    assert all(0.95 * 0.05 <= g <= 0.06 for g in gaps)


def test_top_gaps_are_the_sleeps(red):
    gaps = bt.top_gaps(red, lambda t: "host", n=6)
    assert sorted(s for _, s in gaps) == pytest.approx(
        sorted((b - a) / 1e9 for a, b in red["devices"][0]["gaps"]
               if b - a > 1e6), abs=1e-9)


def test_self_seconds_leave_out_nested_ops():
    ev = [(0, 100, "while"), (10, 20, "a"), (30, 60, "b"), (40, 50, "c"),
          (120, 130, "d")]
    got = bt.self_seconds(ev)
    assert got == pytest.approx({"while": 60e-9, "a": 10e-9, "b": 20e-9,
                                 "c": 10e-9, "d": 10e-9})
    assert bt.union([(a, b) for a, b, _ in ev]) == [(0, 100), (120, 130)]
