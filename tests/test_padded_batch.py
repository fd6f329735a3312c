"""The batch axis a ladder launch pads to (``parallel.batch.padded_batch``).

Narrow rungs pad to a power of two of at least 8 lanes, so small
launches share one compiled shape; wide rungs (``WIDE_CAPACITY`` rows a
lane and up) pad to their live lanes' own power of two, since there
each padding lane, a copy of the last live one, does a live lane's
work.  The arithmetic is checked without a compile; one ladder run
checks that a key's verdict and evidence do not depend on the lanes it
was launched beside.
"""

from __future__ import annotations

import types

import numpy as np
import pytest

from jepsen_tpu.parallel import batch


def mesh_of(devices: int):
    """What ``padded_batch`` reads of a mesh: its device count."""
    return types.SimpleNamespace(devices=np.zeros(devices))


@pytest.mark.parametrize("capacity", [None, 1, 16, 64, 256, 511])
@pytest.mark.parametrize("n, want", [(1, 8), (3, 8), (8, 8), (9, 16), (33, 64)])
def test_narrow_rungs_keep_the_floor_of_eight(capacity, n, want):
    assert batch.padded_batch(n, None, capacity) == want


@pytest.mark.parametrize("capacity", [512, 4096])
@pytest.mark.parametrize("n, want", [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8),
                                     (8, 8), (9, 16)])
def test_wide_rungs_pad_to_their_live_lanes(capacity, n, want):
    assert batch.padded_batch(n, None, capacity) == want


@pytest.mark.parametrize("n, capacity, devices, want", [
    (1, 4096, 4, 4),  # one live lane still fills the mesh
    (3, 4096, 4, 4),
    (5, 512, 4, 8),
    (3, 512, 6, 6),  # a power of two rounded up to the mesh multiple
    (5, 512, 6, 12),
    (1, 64, 4, 8),
    (1, 64, 6, 12),
])
def test_the_mesh_multiple_holds(n, capacity, devices, want):
    assert batch.padded_batch(n, mesh_of(devices), capacity) == want


@pytest.mark.parametrize("n, capacity, pad_to, want", [
    (1, 4096, 8, 8),  # a pinned serving width wins over a wide rung's own
    (3, 512, 16, 16),
    (1, 64, 32, 32),
    (9, 512, 8, 16),  # a pin narrower than the live lanes never cuts them
    (3, 512, None, 4),
])
def test_a_pinned_width_still_wins(n, capacity, pad_to, want):
    assert batch.padded_batch(n, None, capacity, pad_to) == want


def test_the_serving_default_is_unchanged():
    # the serving layer reports occupancy with no capacity: floor 8
    assert [batch.padded_batch(n) for n in (1, 2, 7, 8, 9)] == [8, 8, 8, 8, 16]


@pytest.mark.parametrize("keys, capacity, widest", [
    (1, 512, 1), (3, 512, 4), (3, 256, 8),
    (40, 512, 32),  # 16384 rows a launch: 32 lanes, then 8
    (3, 4096, 1),  # a serial rung: one lane a launch
])
def test_the_exact_guard_counts_the_lanes_the_launch_holds(
        monkeypatch, keys, capacity, widest):
    """The exact rung's fault guard and its launches read one padded
    width; the runner is a stand-in, so nothing compiles."""
    from jepsen_tpu import models as m
    from jepsen_tpu.ops import wgl
    from benchmark.gen import histories as gh

    guarded, launched = [], []

    def scan_safe(B, cap, lanes=1):
        guarded.append(lanes)
        return True

    def exact_runner(step, cap, rounds, P, G, W, dedup):
        def run(*args):
            n_pad = args[0].shape[0]
            launched.append(n_pad)
            return (np.ones(n_pad, bool), np.full(n_pad, -1, np.int32),
                    np.zeros(n_pad, bool), np.ones(n_pad, np.int32))
        return run

    monkeypatch.setattr(wgl, "exact_scan_safe", scan_safe)
    monkeypatch.setattr(wgl, "exact_batched_runner", exact_runner)
    subs, _ = gh.key_histories(7, 0, keys, {"ops": 10, "procs": 3,
                                            "info_rate": 0.0, "values": 5})
    out = batch.batch_analysis(
        m.model("cas-register"), subs, capacity=(),
        exact_escalation=(capacity,), greedy_first=False,
        cpu_fallback=False, confirm_refutations=False)
    assert all(r["valid?"] is True for r in out)
    # the guard reads the stage's widest launch; with one chunk that is
    # the only launch
    assert guarded and launched and set(guarded) == {max(launched)}
    assert max(launched) == widest


#: the tutorial register's shape (10 threads and 100 ops a key), every
#: other key corrupted; these three are each refuted at rung 512
TUTORIAL = {"ops": 100, "procs": 10, "info_rate": 0.1, "values": 5,
            "corrupt_every": 2}


@pytest.mark.parametrize("serial, beside_slots", [(False, [4]),
                                                  (True, [1, 1, 1])])
def test_a_key_alone_and_beside_others_gets_the_same_verdict_and_evidence(
        monkeypatch, tmp_path, serial, beside_slots):
    """Through rung 512 in one launch, and with that rung made serial
    (one lane a launch at the group's shape, as rung 4096 runs, which
    is too slow here)."""
    from benchmark.gen import histories as gh
    from jepsen_tpu import obs
    from jepsen_tpu.checker.linearizable import Linearizable

    if serial:
        monkeypatch.setattr(batch, "SERIAL_CAPACITY", 512)
    a, _ = gh.key_histories(1, 0, 4, TUTORIAL)
    b, _ = gh.key_histories(5, 0, 4, TUTORIAL)
    three = [a[1], a[3], b[3]]
    chk = Linearizable({"model": "cas-register",
                        "kernel-opts": {"capacity": (16, 512)}})

    def check(histories, name):
        test = {"name": name, "start-time-str": "t0",
                "store-dir": str(tmp_path)}
        with obs.recording(tmp_path / name) as rec:
            out = chk.check_batch(test, histories, {})
        return out, [e["attrs"] for e in rec.events
                     if e.get("type") == "span" and e["name"] == "ladder.launch"
                     and e["attrs"]["capacity"] == 512]

    (alone,), alone_launches = check(three[:1], "alone")
    beside, launches = check(three, "beside")
    # the wide rung ran each at its own width
    assert [a["padded"] for a in alone_launches] == [1]
    assert [a["padded"] for a in launches] == beside_slots
    # the lanes differ in shape; launched one at a time they share the
    # group's, so at most the first compiles
    assert sum(a["compiled"] for a in launches) <= 1
    assert alone["valid?"] is False
    assert beside[0]["valid?"] is alone["valid?"]
    assert beside[0]["evidence"]["digest"] == alone["evidence"]["digest"]
    assert [r["valid?"] for r in beside] == [False, False, False]
