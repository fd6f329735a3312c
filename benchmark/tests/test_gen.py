"""The benchmark's copy of the generator reproduces the program's, and
the reference decides every generated history correctly."""

import sys
from pathlib import Path

import pytest

from benchmark import reference
from benchmark.gen import histories as gh

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "tools"))
import genhist  # noqa: E402


@pytest.mark.parametrize("seed", [0, 7, 2_147_483_659, 2**40 + 3])
@pytest.mark.parametrize("shape", [(100, 8, 0.3, 8), (20, 5, 0.1, 5),
                                   (1, 5, 0.1, 5)])
def test_copy_reproduces_genhist(seed, shape):
    n_ops, procs, info, values = shape
    want = genhist.valid_register_history(n_ops, procs, seed=seed,
                                          info_rate=info, n_values=values)
    got = gh.register_history(n_ops, procs, seed, info, values, landed=set())
    assert got == want
    assert gh.corrupt(got, seed) == genhist.corrupt(want, seed=seed)


@pytest.mark.parametrize("seed", range(40))
def test_reference_decides_generated_histories(seed):
    landed: set = set()
    hist = gh.register_history(60, 6, seed, 0.3, 5, landed=landed)
    assert reference.verdict(hist, landed) is True
    bad = gh.corrupt(hist, seed)
    if bad != hist:
        assert reference.verdict(bad, landed) is False
    # a witness that leaves out a landed write is caught
    if landed:
        assert reference.verdict(hist, set()) in (True, None)


def test_keyed_history_splits_back():
    shape = {"ops": 20, "procs": 4, "info_rate": 0.2, "values": 5,
             "corrupt_every": 3}
    subs, _ = gh.key_histories(5, 0, 6, shape)
    labels = [3, 0, 5, 1, 4, 2]
    keyed = gh.keyed_history(subs, 4, labels)
    assert [o["index"] for o in keyed] == list(range(len(keyed)))
    for i, k in enumerate(labels):
        mine = [o for o in keyed if o["value"][1] == k]
        assert [o["value"][2] for o in mine] == [o["value"] for o in subs[i]]
        assert {o["process"] // 4 for o in mine} == {k}


def test_pool_is_drawn_from_the_seed():
    """Each seed draws its own histories of one shape; a seed gives the
    same histories every time, even one that 32 bits cannot hold."""
    shape = {"ops": 20, "procs": 5, "info_rate": 0.1, "values": 5,
             "corrupt_every": 50}
    seed = 2**33 + 5
    a, _ = gh.key_histories(seed, 0, 8, shape)
    assert gh.key_histories(seed, 0, 8, shape)[0] == a
    assert gh.key_histories(seed, 1, 8, shape)[0] != a
    assert gh.key_histories(seed + 1, 0, 8, shape)[0] != a
    assert all(sum(o["type"] == "invoke" for o in h) == 20 for h in a)
