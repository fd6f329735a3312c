"""Cells at a size a test run on the CPU holds, and a helper that runs them."""

from __future__ import annotations

import json

from benchmark import run

#: the overrides that shrink each cell's configuration and traffic; one
#: key in four corrupted, so that a few keys suffice to hold refutations
SMALL = {
    "defaults-oneshot": {"config": {"keys": 16, "corrupt_every": 4},
                         "traffic": {"pool": 2}},
}
SECONDS = {"defaults-oneshot": 1.0}


def spec() -> dict:
    return json.loads((run.ROOT / "BENCHMARK.json").read_text())


def cell(name: str, seed: int, trace: bool = False) -> run.Cell:
    c = run.Cell(spec(), name, seed, SECONDS[name], trace)
    for part, kv in SMALL[name].items():
        getattr(c, part).update(kv)
    return c


def result_line(name: str, seed: int) -> dict:
    args = run.parse_args(["--workload", name, "--seed", str(seed),
                           "--seconds", str(SECONDS[name]), "--trace", "0"])
    return run.run_cell(args, spec(), allow_cpu=True, overrides=SMALL[name])
