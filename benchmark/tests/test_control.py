"""The control, the ladder cut to one configuration with its losses read
as refutations, fails each cell's comparison at a small size."""

import pytest

from benchmark import control, run
from benchmark.tests import small

import jax


@pytest.mark.parametrize("name", sorted(small.SMALL))
@pytest.mark.parametrize("seed", [2_147_483_659, 2_200_000_017])
def test_control_gives_wrong_verdicts(name, seed):
    cell = small.cell(name, seed)
    if cell.chips > len(jax.devices()):
        pytest.skip(f"{name} needs {cell.chips} devices")
    control.cut(cell)
    loop = run.load_module(run.BENCH / "loops" / f"{cell.traffic['loop']}.py",
                           "benchmark_loop")
    _, devs = run.device_info(cell.chips, allow_cpu=True)
    try:
        counts = control.run_control(cell, devs, loop)
    finally:
        run.stop_children()
    assert counts["wrong"] > run.LIMITS["wrong_verdicts"], counts
