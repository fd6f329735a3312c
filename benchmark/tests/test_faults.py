"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of a cell at a small size on the CPU (the
harness's look for a chip skipped), with one fault planted in the
program's ladder, and requires ``correct`` to be false:

* ``step_unchanged``: the frontier step returns its state unchanged and
  refuses no op, so the greedy walk finishes every lane;
* ``half_batch``: every other history of a launch is left out and
  given the answer of the history before it;
* ``answer_altered``: the first answer of every launch is flipped where
  the ladder produces it;
* ``answer_unknown``: the first answer of every launch is turned into
  ``unknown``, a lane given up undecided.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

from benchmark.tests import small

FAULTS = ("step_unchanged", "half_batch", "answer_altered", "answer_unknown")
CELL = "defaults-oneshot"


@contextlib.contextmanager
def broken_ladder(keep, outer_of, fix=lambda j, r: r):
    """Replace the program's ``batch_analysis`` (under both names its
    callers use) with one that checks ``histories[keep(n)]`` and hands
    inner answer ``i`` to the outer positions ``outer_of(i, n)``, each
    through ``fix``."""
    import jepsen_tpu.parallel as par
    from jepsen_tpu.parallel import batch

    orig = batch.batch_analysis

    def ladder(model, histories, *a, **kw):
        histories = list(histories)
        n = len(histories)
        inner = [histories[i] for i in keep(n)]
        res = orig(model, inner, *a, **kw)
        out = {}
        for i, r in enumerate(res):
            for j in outer_of(i, n):
                out[j] = fix(j, dict(r))
        return [out[j] for j in sorted(out)]

    par.batch_analysis = batch.batch_analysis = ladder
    try:
        yield
    finally:
        par.batch_analysis = batch.batch_analysis = orig


def _flip(j, r):
    if j == 0 and r.get("valid?") in (True, False):
        r["valid?"] = not r["valid?"]
    return r


def _unknown(j, r):
    if j == 0:
        r["valid?"] = "unknown"
    return r


@contextlib.contextmanager
def step_unchanged():
    from jepsen_tpu.ops import wgl

    orig = wgl.greedy_runner

    def runner_factory(*a, **kw):
        def runner(*args):
            n = np.asarray(args[1]).shape[0]
            return (np.ones(n, bool), np.zeros(n, np.int32),
                    np.zeros(n, np.int32))
        return runner

    wgl.greedy_runner = runner_factory
    try:
        yield
    finally:
        wgl.greedy_runner = orig


def plant(fault: str):
    if fault == "step_unchanged":
        return step_unchanged()
    if fault == "half_batch":
        return broken_ladder(
            lambda n: range(0, n, 2),
            lambda i, n: [2 * i] + ([2 * i + 1] if 2 * i + 1 < n else []))
    if fault == "answer_altered":
        return broken_ladder(lambda n: range(n), lambda i, n: [i], _flip)
    if fault == "answer_unknown":
        return broken_ladder(lambda n: range(n), lambda i, n: [i], _unknown)
    raise ValueError(fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_makes_run_incorrect(fault):
    with plant(fault):
        line = small.result_line(CELL, 2_147_483_700)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("seed", [2_147_483_701, 3_000_000_019])
def test_sound_run_is_correct(seed):
    line = small.result_line(CELL, seed)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
