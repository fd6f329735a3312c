"""The canonical encoders give the bytes they always gave.

``store._jsonable``, ``checkpoint.fingerprint``, ``durable.canonical_bytes``
and ``provenance._strip`` dispatch on a value's exact type before the ABC
chain.  Their output is a contract: durable envelopes on disk carry a CRC
over ``canonical_bytes``, resume compares ``fingerprint``, and the serving
layer keys idempotency and poison quarantine on it.  This file holds
frozen copies of the encoders as they were before the exact-type paths
and compares them with the live ones over a corpus of values, real
generated histories among them.
"""

from __future__ import annotations

import collections
import enum
import hashlib
import json
import zlib
from typing import Any, Mapping

import numpy as np
import pytest

from benchmark.gen import histories as gh
from jepsen_tpu import history as h
from jepsen_tpu import independent, store
from jepsen_tpu import models as m
from jepsen_tpu.obs import provenance
from jepsen_tpu.store import checkpoint, durable
from jepsen_tpu.store import format as fmt

# ---------------------------------------------------------------------------
# The encoders as they were (frozen: never edit these to follow the code)
# ---------------------------------------------------------------------------


def _old_jsonable(x: Any):
    if isinstance(x, Mapping):
        return {str(k): _old_jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple, set, frozenset)):
        return [_old_jsonable(v) for v in x]
    if isinstance(x, h.ColumnHistory):
        return [_old_jsonable(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)


def _old_fingerprint(histories) -> str:
    hh = hashlib.sha256()
    for hist in histories:
        hh.update(b"\x00")
        cols = getattr(hist, "cols", None)
        if cols is not None and hasattr(hist, "fs"):
            for name in ("type", "process", "f", "value1", "value2"):
                if name in cols:
                    hh.update(np.ascontiguousarray(np.asarray(cols[name])).tobytes())
            hh.update(json.dumps(list(hist.fs), default=str).encode())
            extras = getattr(hist, "extras", None) or {}
            if extras:
                hh.update(
                    json.dumps(_old_jsonable(extras), sort_keys=True,
                               default=str).encode()
                )
            continue
        for o in hist:
            hh.update(
                json.dumps(
                    [
                        _old_jsonable(o.get("type")),
                        _old_jsonable(o.get("process")),
                        _old_jsonable(o.get("f")),
                        _old_jsonable(o.get("value")),
                    ],
                    separators=(",", ":"),
                    default=str,
                ).encode()
            )
    return hh.hexdigest()


def _old_strip(x):
    if isinstance(x, Mapping):
        return {
            str(k): _old_strip(v) for k, v in x.items()
            if str(k) not in provenance._DIGEST_STRIP
        }
    if isinstance(x, (list, tuple)):
        return [_old_strip(v) for v in x]
    return x


def _old_canonical_bytes(payload) -> bytes:
    return json.dumps(
        _old_jsonable(payload), sort_keys=True, separators=(",", ":"),
        default=str,
    ).encode()


def _old_bundle_digest(payload: Mapping) -> str:
    core = {
        "history_fingerprint": payload.get("history_fingerprint"),
        "verdict": payload.get("verdict"),
        "cause": provenance._stable_cause(payload.get("cause")),
        "model": payload.get("model"),
        "checker": payload.get("checker"),
        "decision_path": _old_strip(payload.get("decision_path") or []),
        "engine": _old_strip(payload.get("engine") or {}),
        "config": _old_strip(payload.get("config") or {}),
        "witness": _old_strip(payload.get("witness") or {}),
    }
    return hashlib.sha256(_old_canonical_bytes(core)).hexdigest()


# ---------------------------------------------------------------------------
# The corpus
# ---------------------------------------------------------------------------

#: the provenance block the ladder attaches to a key's result
LADDER_PROV = {
    "path": [{"event": "ladder.stage", "stage": 0, "engine": "greedy",
              "capacity": 1, "outcome": "valid", "seconds": 0.25}],
    "engine": {"engine": "async", "dedup_backend": "sort",
               "greedy_first": True},
    "config": {"engine": "async", "capacity": [64, 512, 4096],
               "exact_escalation": [], "rounds": 8, "greedy_first": True,
               "carry_frontier": True, "dedup": "sort",
               "confirm_refutations": True, "frontier_budget_mb": None,
               "fingerprint": "batch"},
}


class _Color(enum.IntEnum):
    RED = 1
    BLUE = 2


class _Name(str):
    pass


class _Record(dict):
    pass


class _Thing:
    def __str__(self):
        return "thing<7>"


def _defaults_subhistories(_tmp):
    """The subhistories of a 50-key history of the defaults cell's shape."""
    subs, _ = gh.key_histories(2147490411, 0, 50, {
        "ops": 20, "procs": 5, "info_rate": 0.1, "values": 5,
        "corrupt_every": 50})
    _, split, _ = independent.split(gh.keyed_history(subs, 5))
    return split


def _column_history(tmp):
    hist = []
    for i in range(12):
        p = i % 3
        hist.append(h.op(h.INVOKE, p, "write", i % 5))
        hist.append(h.op(h.OK, p, "write", i % 5))
    hist.append(h.op(h.INFO, h.NEMESIS, "kill", {"n1": "killed"}))
    hist.append(h.op(h.INVOKE, 0, "cas", [1, 2]))
    hist.append(h.op(h.OK, 0, "cas", [1, 2]))
    hist = h.index([{**o, "time": k} for k, o in enumerate(hist)])
    f = tmp / "run.jepsen"
    w = fmt.Writer(f)
    w.write_test({"name": "canonical", "start-time-str": "t"})
    w.write_history(hist)
    w.write_results({"valid?": True})
    w.close()
    return h.ColumnHistory(*fmt.read_columns(f))


#: case name -> a function of ``tmp_path`` that makes the value
CASES = {
    "defaults-subhistories": _defaults_subhistories,
    "tuples-in-lists": lambda _: [(1, 2), [(3, (4, 5))], {"k": (6, [7])}],
    "sets": lambda _: {"s": {3, 1, 2}, "fs": frozenset({"a", "b"}),
                       "in": [{(1, 2)}]},
    "numpy": lambda _: {"i": np.int64(7), "f": np.float32(0.1),
                        "a": np.arange(6).reshape(2, 3),
                        "b": np.array([1.5, float("nan")]),
                        "l": [np.int32(-3), np.float64(2.5)]},
    "ordered-dict": lambda _: collections.OrderedDict(
        [("b", 1), ("a", (2, 3)), ("seconds", 0.5),
         ("c", collections.OrderedDict(z=None, id="x"))]),
    "dict-subclass": lambda _: _Record(x=1, lanes=8,
                                       y=[_Record(z="w", mode="worker")]),
    "non-str-keys": lambda _: {3: "int", True: "bool", None: "none",
                               2.5: "float", (1, 2): "tuple"},
    "int-enum": lambda _: {"c": _Color.RED, "l": [_Color.BLUE],
                           _Color.BLUE: "key"},
    "str-subclass": lambda _: {_Name("k"): _Name("v"), "l": [_Name("w")]},
    "big-and-special-numbers": lambda _: [10**30, -10**30, float("inf"),
                                          float("-inf"), float("nan"),
                                          -0.0, 1e-300],
    "scalars": lambda _: [True, False, None, 0, 1, -1, 0.0, "",
                          "é ☃ \"quoted\"\n", b"bytes"],
    "arbitrary-objects": lambda _: {"t": _Thing(), "m": m.CASRegister(3),
                                    "l": [_Thing(), {"o": _Thing()}]},
    "stripped-keys": lambda _: {"seconds": 1.5, "lanes": 8, "keep": {
        "id": "x", "mode": "worker", "outcome": ("valid", 1)}},
    "column-history": _column_history,
}


def _histories(case, x):
    """What ``fingerprint`` gets for a case: its histories, else one
    history with the value in every hashed field of an op."""
    if case == "defaults-subhistories":
        return x
    if case == "column-history":
        return [x]
    return [[{"type": x, "process": x, "f": x, "value": x},
             {"type": "ok", "process": 0, "f": "read", "value": x}]]


def _bundles(case, x, hists):
    """Bundles over the case's histories, as the checker builds them, and
    one payload with the value in every field of the digest's core."""
    out = []
    for i, hist in enumerate(hists):
        refuted = isinstance(hist, list) and case != "defaults-subhistories"
        result = ({"valid?": False, "op": hist[0]} if refuted
                  else {"valid?": True})
        result["provenance"] = LADDER_PROV
        out.append(provenance.build_bundle(
            history=hist, result=result, source="check_batch",
            model=m.CASRegister(None), checker="linearizable",
            bundle_id=f"b{i}"))
    out.append({"history_fingerprint": "f", "verdict": "true", "cause": x,
                "model": "cas-register", "checker": "linearizable",
                "decision_path": [{"event": "e", "v": x, "seconds": 1.0}],
                "engine": {"e": x}, "config": {"c": x}, "witness": {"w": x}})
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_encoders_give_the_frozen_bytes(case, tmp_path):
    x = CASES[case](tmp_path)
    # repr tells list from tuple, bool from int and dict from its subclasses
    assert repr(store._jsonable(x)) == repr(_old_jsonable(x))
    assert durable.canonical_bytes(x) == _old_canonical_bytes(x)
    assert repr(provenance._strip(x)) == repr(_old_strip(x))
    hists = _histories(case, x)
    assert checkpoint.fingerprint(hists) == _old_fingerprint(hists)
    for hist in hists:
        assert provenance.history_fingerprint(hist) == _old_fingerprint([hist])
    for b in _bundles(case, x, hists):
        assert provenance.bundle_digest(b) == _old_bundle_digest(b)
        if "digest" in b:
            assert b["digest"] == _old_bundle_digest(b)
            assert durable.canonical_bytes(b) == _old_canonical_bytes(b)


def test_the_defaults_bundles_carry_witnesses(tmp_path):
    """The corpus reaches the witness walk: valid keys of the defaults
    shape carry a linearization, so their digests hash one."""
    hists = _defaults_subhistories(tmp_path)
    bundles = _bundles("defaults-subhistories", hists, hists)[:-1]
    walked = [b for b in bundles if (b["witness"] or {}).get("type")
              == "linearization"]
    assert len(walked) >= len(hists) // 2


@pytest.mark.parametrize("case", ["defaults-subhistories", "numpy",
                                  "non-str-keys", "big-and-special-numbers"])
def test_an_envelope_written_the_old_way_reads_verified(case, tmp_path):
    """A bundle enveloped with the frozen ``canonical_bytes`` CRC, as a
    file on disk from before the exact-type paths, passes
    ``read_verified`` under the live code."""
    x = CASES[case](tmp_path)
    for i, b in enumerate(_bundles(case, x, _histories(case, x))):
        # the payload as the file holds it: through the old encoder and
        # back, so the reader's CRC sees exactly the stored JSON
        payload = json.loads(json.dumps(_old_jsonable(b), default=str))
        doc = {"durable": durable.ENVELOPE, "kind": provenance.KIND_BUNDLE,
               "version": 1,
               "crc32": zlib.crc32(_old_canonical_bytes(payload)) & 0xFFFFFFFF,
               "payload": payload}
        path = tmp_path / f"{case}-{i}.json"
        path.write_text(json.dumps(_old_jsonable(doc), indent=1, default=str))
        got = durable.read_verified(path, provenance.KIND_BUNDLE)
        assert repr(got.payload) == repr(payload)  # repr: nan == nan
        assert not got.legacy and not got.migrated
        assert durable.payload_crc(got.payload) == doc["crc32"]
