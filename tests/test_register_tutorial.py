"""Jepsen's tutorial register shape through the one-shot checker path.

The tutorial's etcd test (doc/tutorial/06-refining.md in jepsen-io/jepsen)
runs 10 threads a key and 100 ops a key, so a key's frontier runs to
thousands of configurations: the greedy walk cannot finish a corrupted
key, and the ladder's beam rungs, the carried frontier and the
confirmation of refutations decide it.  Here a few such keys, crashed
writes and cas among them, go through
``check_safe(independent.checker(linearizable(cas-register)))`` on a
reduced ladder (rungs 64 and 256), and each key's verdict is compared
with the exact CPU sweep and with the generator's certificate.
"""

from __future__ import annotations

import pytest

from benchmark import reference
from benchmark.gen import histories as gh
from jepsen_tpu import checker, independent, obs
from jepsen_tpu import models as m
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.parallel import batch

#: the tutorial's shape (10 threads and 100 ops a key, values 0-4), one
#: op in ten crashed, one key in three corrupted
SHAPE = {"ops": 100, "procs": 10, "info_rate": 0.1, "values": 5,
         "corrupt_every": 3}
KEYS = 6
LADDER = (64, 256)


def spans(rec, name: str) -> list[dict]:
    return [e["attrs"] for e in rec.events
            if e.get("type") == "span" and e["name"] == name]


def check(seed: int, tmp_path):
    subs, landed = gh.key_histories(seed, 0, KEYS, SHAPE)
    chk = independent.checker(linearizable(
        {"model": "cas-register",
         "kernel-opts": {"capacity": LADDER}}))
    with obs.recording(tmp_path) as rec:
        res = checker.check_safe(chk, {}, gh.keyed_history(subs, SHAPE["procs"]))
    return subs, landed, res, rec


@pytest.mark.parametrize("seed", [2, 4, 5, 6])
def test_tutorial_keys_match_the_sweep_and_certificate(seed, tmp_path):
    subs, landed, res, rec = check(seed, tmp_path)
    model = m.model("cas-register")
    got = [res["results"][k]["valid?"] for k in range(KEYS)]
    sweep = [wgl_cpu.sweep_analysis(model, h, max_configs=2_000_000)["valid?"]
             for h in subs]
    assert got == sweep
    certs = [reference.verdict(h, w) for h, w in zip(subs, landed)]
    assert [c for c in certs if c is not None], "no key has a certificate"
    for g, c in zip(got, certs):
        assert c is None or g is c
    assert False in got and res["valid?"] is False
    # the mechanism ran: some key left the greedy walk, some passed the
    # first beam rung
    lanes = {s["capacity"]: s["lanes"] for s in spans(rec, "ladder.stage")}
    assert lanes.get(LADDER[0], 0) > 0 and lanes.get(LADDER[1], 0) > 0


@pytest.mark.parametrize("seed", [1, 2])
def test_undecided_keys_go_to_the_worker_sweep(seed, tmp_path):
    """A key the widest rung leaves lossy is swept whole in the
    confirmation workers, never in the ladder's in-process fallback."""
    _subs, _landed, res, rec = check(seed, tmp_path)
    top = [s for s in spans(rec, "ladder.stage") if s["capacity"] == LADDER[-1]]
    undecided = sum(s["unknowns_remaining"] for s in top)
    assert undecided > 0, "no key outgrew the ladder; pick another seed"
    (drain,) = spans(rec, "ladder.confirm.drain")
    assert drain["undecided"] == undecided
    assert not spans(rec, "ladder.cpu-fallback")
    assert all(r["valid?"] in (True, False) for r in res["results"].values())


def test_undecided_keys_stay_in_process_without_worker_sweeps(tmp_path):
    """Where refutations are not confirmed by worker sweeps, the
    in-process CPU fallback still decides what the ladder leaves."""
    subs, _landed = gh.key_histories(1, 0, KEYS, SHAPE)
    model = m.model("cas-register")
    with obs.recording(tmp_path) as rec:
        out = batch.batch_analysis(model, subs, capacity=LADDER,
                                   confirm_refutations=False)
    assert spans(rec, "ladder.cpu-fallback")
    assert [r["valid?"] for r in out] == [
        wgl_cpu.sweep_analysis(model, h, max_configs=2_000_000)["valid?"]
        for h in subs]


def test_resume_resubmits_an_undecided_keys_sweep(tmp_path):
    """A checkpoint taken while an undecided key's whole-history sweep
    was in flight (no failure barrier) resumes into the same sweep."""
    from jepsen_tpu.store import checkpoint as ckpt

    subs, _landed = gh.key_histories(2, 0, KEYS, SHAPE)
    hist = [subs[2]]  # corrupted, lossy past rung 64
    placeholder = {"valid?": "unknown", "cause": "frontier capacity exhausted"}
    ckpt.save(tmp_path, config={"capacity": [64], "fingerprint":
                                ckpt.fingerprint(hist)},
              stage=2, results={0: placeholder}, pending=[],
              confirms={0: {"res": placeholder, "op_pos": None}})
    model = m.model("cas-register")
    (out,) = batch.batch_analysis(model, hist, capacity=(64,),
                                  checkpoint_dir=tmp_path, resume=True)
    assert out["valid?"] is False
    assert out["valid?"] == wgl_cpu.sweep_analysis(model, hist[0])["valid?"]


@pytest.mark.parametrize("sweep", [True, False, "unknown"])
def test_a_swept_undecided_lane_takes_the_sweeps_verdict(sweep):
    placeholder = {"valid?": "unknown", "cause": "frontier capacity exhausted"}
    swept = {"valid?": sweep, "cause": "from the sweep"}
    assert batch._resolve_confirmation(placeholder, swept) == swept
