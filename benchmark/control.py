"""The control: the cell's loop with the checker cut so that it breaks
the guarantee that a verdict is never wrong.

The cut is the shortcut that would tempt a change made for speed: the
ladder stops at one configuration (a beam of capacity 1), nothing falls
back to the exact CPU sweep, and a lane that lost configurations is read
as a refutation.  The greedy witness walk that the ladder runs first
is switched off too (``batch_analysis(greedy_first=False)``): it decides
nearly every valid key of these shapes alone, and would hide the cut.
Valid keys whose one configuration goes astray then come back false.  The control
must fail the cell's comparison on every seed; the fewest wrong verdicts
it gives is the upper reading of ``wrong_verdicts`` in PERF.md.

    python3 benchmark/control.py --workload CELL --seeds A,B,C --seconds S

runs the control on the chip at the cell's own size, one seed after
another in one process, and prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

#: the cut, per loop: config keys merged over the cell's configuration
CUT = {
    "closed": {"linearizable_opts": {
        "model": "cas-register", "algorithm": "tpu",
        "kernel-opts": {"capacity": [1], "exact_escalation": []}}},
}


@contextlib.contextmanager
def no_greedy_walk():
    """The program's ladder with its greedy walk switched off, under
    both names its callers use."""
    import jepsen_tpu.parallel as par
    from jepsen_tpu.parallel import batch

    orig = batch.batch_analysis
    cut_ladder = functools.partial(orig, greedy_first=False)
    par.batch_analysis = batch.batch_analysis = cut_ladder
    try:
        yield
    finally:
        par.batch_analysis = batch.batch_analysis = orig


def cut(cell) -> None:
    """Apply the control's cut to ``cell``'s configuration."""
    for key, kv in CUT[cell.traffic["loop"]].items():
        cell.config[key] = {**cell.config.get(key, {}), **kv}


def as_refutation(verdict):
    """A lane the cut ladder could not decide, read as a refutation."""
    return False if verdict == "unknown" else verdict


def run_control(cell, devs, loop) -> dict:
    """One control run of ``cell`` (already cut): the loop's output with
    its verdicts read the control's way, compared with the reference."""
    from benchmark import reference

    with no_greedy_walk():
        out = loop.run(cell, devs)
    verdicts = [as_refutation(v) for v in out["verdicts"]]
    truths = [reference.verdict(h, w) for h, w in out["truth"]]
    return reference.compare(verdicts, truths)


def main() -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    run.os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            cell = run.Cell(spec, args.workload, seed, args.seconds, False)
            cut(cell)
            loop = run.load_module(
                run.BENCH / "loops" / f"{cell.traffic['loop']}.py",
                "benchmark_loop")
            _, devs = run.device_info(cell.chips)
            from jepsen_tpu import compile_cache

            compile_cache.enable()
            counts = run_control(cell, devs, loop)
            print(json.dumps({"seed": seed, **counts}), flush=True)
    finally:
        run.stop_children()
    return 0


if __name__ == "__main__":
    sys.exit(main())
