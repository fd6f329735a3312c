"""The tutorial cell at a size the CPU holds, and what it guards.

``tutorial-oneshot`` checks keys of 100 ops and 10 processes, whose
corrupted keys the greedy walk cannot finish and the first beam rung
cannot refute.  A small run here keeps that shape (6 keys, one in three
corrupted) on a reduced ladder, rungs 64 and 256, passed through the
configuration's ``kernel-opts``.  The planted fault breaks only the
widest rung: its launches report each refutation as a surviving
frontier.  The tutorial run comes out not correct; the defaults run,
whose 20-op keys are all refuted at rung 64, stays correct under the
same fault, so only the new cell guards the wide rungs.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

from benchmark import run
from benchmark.tests import small

LADDER = [64, 256]
OPTS = {"model": "cas-register", "kernel-opts": {"capacity": LADDER}}
SMALL = {
    "tutorial-oneshot": {"config": {"keys": 6, "corrupt_every": 3,
                                    "linearizable_opts": OPTS},
                         "traffic": {"pool": 2}},
    "defaults-oneshot": {"config": {**small.SMALL["defaults-oneshot"]["config"],
                                    "linearizable_opts": OPTS},
                         "traffic": small.SMALL["defaults-oneshot"]["traffic"]},
}


def result_line(name: str, seed: int) -> dict:
    args = run.parse_args(["--workload", name, "--seed", str(seed),
                           "--seconds", "1", "--trace", "0"])
    return run.run_cell(args, small.spec(), allow_cpu=True,
                        overrides=SMALL[name])


@contextlib.contextmanager
def widest_rung_never_refutes():
    """Launches at the ladder's widest rung report every lossless
    refutation as a frontier that survived: valid, no failure."""
    from jepsen_tpu.ops import wgl

    orig = wgl.async_runner

    def runner_factory(step, F, *a, **kw):
        runner = orig(step, F, *a, **kw)
        if F != LADDER[-1]:
            return runner

        def broken(*args):
            valid, failed_at, lossy, *rest = runner(*args)
            refuted = (np.asarray(failed_at) >= 0) & ~np.asarray(lossy)
            return (np.asarray(valid) | refuted,
                    np.where(refuted, -1, np.asarray(failed_at)),
                    lossy, *rest)
        return broken

    wgl.async_runner = runner_factory
    try:
        yield
    finally:
        wgl.async_runner = orig


@pytest.mark.parametrize("seed", [2_147_483_704, 3_000_000_016])
def test_small_tutorial_run_is_correct(seed):
    line = result_line("tutorial-oneshot", seed)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0


def test_a_wide_rung_fault_only_the_tutorial_cell_sees():
    with widest_rung_never_refutes():
        tutorial = result_line("tutorial-oneshot", 2_147_483_704)
        defaults = result_line("defaults-oneshot", 2_147_483_704)
    assert tutorial["correct"] is False, tutorial["compared"]
    assert tutorial["compared"]["wrong_verdicts"]["value"] > 0
    assert defaults["correct"] is True, defaults["compared"]
