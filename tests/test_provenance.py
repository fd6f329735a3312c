"""Verdict provenance: evidence bundles, verify, and audit replay
(jepsen_tpu/obs/provenance.py + tools/evidence.py).

Kernel shapes are shared with tests/test_parallel.py / test_serve.py —
(30, 3) register histories at capacity (64, 256) — so every ladder
launch here re-hits runner caches the suite already paid to compile
(tier-1 budget is tight).  Chunked-path coverage reuses test_spill's
[64] capacity on a 4-op register history.
"""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))

from genhist import corrupt, valid_register_history
from jepsen_tpu import faults, history as h, obs
from jepsen_tpu import models as m
from jepsen_tpu import serve as sv
from jepsen_tpu.checker import elle
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.obs import provenance
from jepsen_tpu.store import durable

#: the suite-shared ladder (same shapes as test_parallel/test_serve).
CAP = (64, 256)


def _test_map(tmp_path, name="prov"):
    return {"name": name, "start-time-str": "t0", "store-dir": str(tmp_path)}


def _bundles(tmp_path, name="prov"):
    return list(provenance.iter_bundles(tmp_path / name / "t0"))


def _checker():
    return Linearizable({"model": "cas-register",
                         "kernel-opts": {"capacity": CAP}})


# ---------------------------------------------------------------------------
# Bundle completeness across the verdict paths
# ---------------------------------------------------------------------------


def test_check_emits_complete_bundle(tmp_path):
    """One-shot check: the result carries the evidence pointer and the
    on-disk bundle holds the full decision record — path, engine,
    fingerprints, a re-steppable witness, and a digest that
    recomputes."""
    hist = valid_register_history(30, 3, seed=1, info_rate=0.1)
    res = _checker().check(_test_map(tmp_path), hist, {})
    assert res["valid?"] is True
    ev = res["evidence"]
    bundle = provenance.read_bundle(ev["path"])
    assert bundle["id"] == ev["id"]
    assert bundle["digest"] == ev["digest"]
    for field in provenance._REQUIRED:
        assert bundle.get(field) is not None, field
    assert bundle["source"] == "check"
    assert bundle["verdict"] == "true"
    assert bundle["checker"] == "linearizable"
    assert bundle["model"] == m.CASRegister(None).name
    assert bundle["history_fingerprint"] == provenance.history_fingerprint(hist)
    assert bundle["decision_path"], "empty decision path"
    assert bundle["engine"].get("engine")
    assert bundle["machine"]
    # the witness is a full linearization order verify can re-step
    assert bundle["witness"]["type"] == "linearization"
    assert bundle["witness"]["order"]
    assert provenance.bundle_digest(bundle) == bundle["digest"]
    rep = provenance.verify_bundle(bundle)
    assert rep["ok"], rep
    assert "witness-linearization" in rep["checks"]


def test_check_batch_ladder_bundles_verify_and_replay(tmp_path):
    """The ladder path: every history in a check_batch lands its own
    bundle (valid AND refuted), each verifies, and each replays to the
    identical verdict under the recorded capacity ladder."""
    hists = [valid_register_history(30, 3, seed=3, info_rate=0.1),
             corrupt(valid_register_history(30, 3, seed=4, info_rate=0.1),
                     seed=4)]
    outs = _checker().check_batch(_test_map(tmp_path), hists, {})
    verdicts = [r["valid?"] for r in outs]
    assert verdicts[0] is True and verdicts[1] is False
    got = _bundles(tmp_path)
    assert len(got) == 2
    by_fp = {b["history_fingerprint"]: b for _, b in got}
    for hist, out in zip(hists, outs):
        b = by_fp[provenance.history_fingerprint(hist)]
        assert b["source"] == "check_batch"
        assert b["verdict"] == provenance.verdict_str(out["valid?"])
        # the ladder recorded its config: replay can pin the same rungs
        assert tuple(b["config"]["capacity"]) == CAP
        assert b["engine"].get("dedup_backend")
        rep = provenance.verify_bundle(b)
        assert rep["ok"], rep
        rr = provenance.replay_bundle(b)
        assert rr["ok"], rr
        assert rr["replayed"] == b["verdict"]
    # the refuted bundle's witness is the killing op
    ref = by_fp[provenance.history_fingerprint(hists[1])]
    assert ref["witness"]["type"] == "refutation"


def test_replay_refuses_bundle_pinned_to_removed_engine(tmp_path):
    """A bundle pinned to a removed ladder path (here the barrier-scan
    "sync" engine) is not replayed under the remaining ladder and
    called a match: replay reports an error naming the path."""
    hist = valid_register_history(30, 3, seed=3, info_rate=0.1)
    _checker().check_batch(_test_map(tmp_path), [hist], {})
    (_, b), = _bundles(tmp_path)
    assert provenance.replay_bundle(b)["ok"]
    pinned = {**b, "engine": {**b["engine"], "engine": "sync"},
              "config": {**b["config"], "engine": "sync"}}
    rr = provenance.replay_bundle(pinned)
    assert not rr["ok"], rr
    assert rr["replayed"] is None
    assert any("removed ladder path" in e and "sync" in e
               for e in rr["errors"]), rr


def test_degraded_unknown_replays_deterministically(tmp_path):
    """A deadline-tripped unknown records the trip on its decision path
    and replays under a pinned zero budget — the degraded outcome is
    reproduced, not raced."""
    hist = valid_register_history(30, 3, seed=5, info_rate=0.1)
    res = _checker().check(_test_map(tmp_path), hist,
                           {"deadline": faults.Deadline(0.0)})
    assert res["valid?"] == "unknown"
    (_, bundle), = _bundles(tmp_path)
    assert bundle["verdict"] == "unknown"
    events = [e["event"] for e in bundle["decision_path"]]
    assert any(ev.startswith("fault.deadline") for ev in events), events
    rr = provenance.replay_bundle(bundle)
    assert rr["ok"], rr
    assert rr["pinned"]["zero_deadline"] is True
    assert rr["replayed"] == "unknown"


def test_chunked_path_records_trajectory():
    """The chunked exact engine threads its per-chunk trajectory into
    the in-memory provenance block even without store coordinates."""
    from jepsen_tpu.ops import wgl

    model = m.CASRegister(None)
    hist = h.index([
        h.op(h.INVOKE, 1, "write", 7, time=1),
        h.op(h.INVOKE, 0, "read", None, time=2),
        h.op(h.OK, 0, "read", 7, time=3),
        h.op(h.INFO, 1, "write", 7, time=4),
    ])
    res = wgl.chunked_analysis(model, hist, wgl.pack(model, hist), [64])
    assert res["valid?"] is True
    prov = res["provenance"]
    events = [e["event"] for e in prov["path"]]
    assert any(ev.startswith("wgl.chunk") for ev in events), events
    assert prov["engine"].get("engine")


def test_elle_graph_bundles_verify_and_replay(tmp_path):
    """The transactional graph path: a G0 refutation bundles its
    anomaly cycles as the witness; verify re-checks cycle closure and
    replay rebuilds the recorded checker + graph engine."""
    hist = []
    for p, value in (
        (0, [["append", "x", 1], ["append", "y", 1]]),
        (1, [["append", "x", 2], ["append", "y", 2]]),
        (2, [["r", "x", [1, 2]], ["r", "y", [2, 1]]]),
    ):
        inv = [[f, k, None if f == "r" else v] for f, k, v in value]
        hist.append({"type": "invoke", "process": p, "f": "txn", "value": inv})
        hist.append({"type": "ok", "process": p, "f": "txn", "value": value})
    for i, op in enumerate(hist):
        op["index"], op["time"] = i, i
    res = elle.list_append().check(_test_map(tmp_path), hist, {})
    assert res["valid?"] is False
    (path, bundle), = _bundles(tmp_path)
    assert bundle["checker"] == "elle-list-append"
    assert bundle["engine"]["engine"] == "elle"
    assert bundle["engine"].get("graph_engine")
    assert bundle["witness"]["type"] == "cycle"
    rep = provenance.verify_bundle(path)
    assert rep["ok"], rep
    assert "witness-cycle" in rep["checks"]
    rr = provenance.replay_bundle(bundle)
    assert rr["ok"], rr
    assert rr["replayed"] == "false"


def test_serve_bundles_ring_and_disk(tmp_path):
    """Every served verdict carries evidence under its request id —
    batched ladder members AND the trivial direct-resolve path — in the
    in-memory ring and, with evidence_dir set, as durable envelopes."""
    ev_dir = tmp_path / "ev"
    svc = sv.CheckService(capacity=CAP, warm_pool=False, evidence_dir=ev_dir)
    hists = [valid_register_history(30, 3, seed=i, info_rate=0.1)
             for i in (6, 7)]
    futs = [svc.submit(hh, client="aud") for hh in hists]
    for _ in range(4):  # the two seeds may pad into different buckets
        if all(f.done() for f in futs):
            break
        svc.step()
    results = [f.result(timeout=10) for f in futs]
    for f, r in zip(futs, results):
        assert r["evidence"]["id"] == f.id
        bundle = svc.get_evidence(f.id)
        assert bundle is not None
        assert bundle["source"] == "serve"
        assert bundle["decision_path"][0]["event"] == "serve.request"
        assert provenance.verify_bundle(bundle)["ok"]
        # the durable copy survives a ring wipe (restart)
        disk = provenance.read_bundle(ev_dir / f"{f.id}.json")
        assert disk["digest"] == bundle["digest"]
    # trivial fast path (resolved at submit, no queue slot)
    f_triv = svc.submit([])
    assert f_triv.done()
    triv = f_triv.result()
    assert triv["evidence"]["id"] == f_triv.id
    b = svc.get_evidence(f_triv.id)
    events = [e["event"] for e in b["decision_path"]]
    assert "serve.trivial" in events, events


# ---------------------------------------------------------------------------
# Tamper rejection
# ---------------------------------------------------------------------------


def test_forged_witness_rejected(tmp_path):
    """A forged linearization — an op deleted from the recorded order,
    digest recomputed so only the witness check can catch it — FAILS
    verification with the missing op named."""
    hist = valid_register_history(30, 3, seed=8, info_rate=0.0)
    _checker().check(_test_map(tmp_path), hist, {})
    (path, bundle), = _bundles(tmp_path)
    order = bundle["witness"]["order"]
    assert len(order) > 1
    forged = dict(bundle)
    forged["witness"] = {"type": "linearization", "order": order[:-1]}
    forged["digest"] = provenance.bundle_digest(forged)
    durable.write_record(path, provenance.KIND_BUNDLE, forged)
    rep = provenance.verify_bundle(path)
    assert rep["ok"] is False
    assert any("witness" in e for e in rep["errors"]), rep


def test_envelope_corruption_quarantined(tmp_path):
    """A byte-flipped envelope fails verify machine-readably and the
    corrupt file is quarantined aside, never silently re-read."""
    hist = valid_register_history(30, 3, seed=9, info_rate=0.1)
    _checker().check(_test_map(tmp_path), hist, {})
    (path, _), = _bundles(tmp_path)
    raw = bytearray(path.read_bytes())
    raw[len(raw) // 2] ^= 0x01
    path.write_bytes(bytes(raw))
    rep = provenance.verify_bundle(path)
    assert rep["ok"] is False
    assert any("envelope" in e for e in rep["errors"]), rep
    assert rep.get("envelope"), "no machine-readable envelope report"
    assert not path.exists()
    assert path.with_name(path.name + ".corrupt-0").exists()
    # quarantined bundles are skipped (with a warning), not re-served
    assert _bundles(tmp_path) == []


# ---------------------------------------------------------------------------
# The offline auditor CLI + the telemetry rollup
# ---------------------------------------------------------------------------


def test_evidence_cli_verify_and_replay(tmp_path, capsys):
    import evidence as evidence_cli

    hists = [valid_register_history(30, 3, seed=10, info_rate=0.1),
             corrupt(valid_register_history(30, 3, seed=11, info_rate=0.1),
                     seed=11)]
    _checker().check_batch(_test_map(tmp_path), hists, {})
    run_dir = str(tmp_path / "prov" / "t0")
    assert evidence_cli.main(["verify", run_dir]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] and rep["mode"] == "verify" and len(rep["bundles"]) == 2
    assert evidence_cli.main(["replay", run_dir]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] and all(b["ok"] for b in rep["bundles"])
    # tampering flips the exit code and the report says why
    (path, bundle), = [x for x in provenance.iter_bundles(tmp_path / "prov" / "t0")][:1]
    forged = dict(bundle)
    forged["verdict"] = "true" if forged["verdict"] != "true" else "false"
    durable.write_record(path, provenance.KIND_BUNDLE, forged)
    assert evidence_cli.main(["verify", str(path)]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is False and rep["bundles"][0]["errors"]


def test_summary_and_trace_summarize_provenance(tmp_path, capsys):
    """The telemetry rollup gains a provenance section and
    trace_summarize --provenance renders the decision-path table."""
    import trace_summarize

    tele = tmp_path / "tele"
    with obs.recording(tele, enabled=True):
        hist = valid_register_history(30, 3, seed=12, info_rate=0.1)
        _checker().check(_test_map(tmp_path), hist, {})
    summary = json.loads((tele / "telemetry.json").read_text())
    pv = summary["provenance"]
    assert pv["bundles"] >= 1
    assert pv["by_source"].get("check") >= 1
    assert pv["by_verdict"].get("true") >= 1
    from jepsen_tpu.obs.summary import format_summary

    assert "verdict provenance" in format_summary(summary)
    rc = trace_summarize.provenance_table(tmp_path / "prov" / "t0",
                                          as_json=True)
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)["provenance"]
    assert len(doc) == 1
    assert doc[0]["verdict"] == "true"
    assert doc[0]["decision_path"]
