"""Verdict provenance: durable evidence bundles + audit verify/replay.

A verdict from this codebase can be produced along very different — and
differently trustworthy — execution paths: three dedup backends, two
elle engines, OOM halving, spill recovery, poison bisection, deadline
degradation and quarantine all alter what actually ran.  This module
gives every verdict (valid / refuted / unknown; one-shot and served) a
single machine-checkable artifact recording *how* it was produced:

* **bundle** — a ``store.durable``-enveloped record holding the history
  fingerprint (``store.checkpoint.fingerprint``), the engine/backend
  resolution (engine, ``dedup_backend``, elle engine, pallas interpret
  flag), the per-rung **decision path** (ladder trajectory, OOM
  halvings, spill retries, confirmations, fallbacks, fault events), the
  witness or refutation payload, the effective config, a machine
  fingerprint, and the linked trace id.
* **digest** — a sha256 over the bundle's *stability core* (fingerprint,
  verdict, decision path, engine, config, witness) with volatile
  attributes stripped — so the same history checked along the same
  decision path yields the same digest whether it was served in a batch
  or replayed sequentially (the loadgen parity cross-check).
* **verify** — structural audit: envelope CRC, digest recompute, and
  witness re-validation against the model (a claimed linearization must
  actually step; a claimed cycle must actually cycle).
* **replay** — re-run the history pinned to the recorded engine /
  backend / config and assert verdict identity.

Producers record path entries via :func:`attach` (pure dict merge, no
I/O) and persist via :func:`emit` / :func:`write_bundle`; both are
best-effort by contract — provenance must never lose a verdict.

Telemetry family ``provenance.*``: ``provenance.bundle`` counts
emissions (attrs ``source``, ``verdict``), ``provenance.emit_error``
counts swallowed emission failures.
"""

from __future__ import annotations

import hashlib
import json
import logging
import time
import uuid
from pathlib import Path
from typing import Mapping, Sequence

from jepsen_tpu import obs
from jepsen_tpu import store as _store

logger = logging.getLogger(__name__)

#: durable envelope kind + payload schema version for evidence bundles.
KIND_BUNDLE = "evidence-bundle"

#: embed the raw history in the bundle when it has at most this many
#: ops (verify/replay then need no sibling files); larger histories
#: keep only the fingerprint and op count.
MAX_EMBED_OPS = 4096

#: decision-path entries kept per bundle; overflow is truncated with a
#: marker entry (a pathological retry loop must not grow an unbounded
#: artifact).
MAX_PATH = 128

#: skip constructive-witness extraction (the greedy re-walk) past this
#: many ops — the walk is linear but the bundle write sits on the
#: serving path.
WITNESS_WALK_MAX_OPS = 2048

#: attribute names stripped (recursively) from the digest's stability
#: core: timings, lane widths, buffer peaks, machine/trace identity —
#: everything that varies between a served batch member and the same
#: history replayed sequentially along the same decision path.
_DIGEST_STRIP = frozenset({
    "seconds", "latency", "lanes", "lanes_from", "lanes_to", "launches",
    "padded", "trace_id", "trace", "machine", "id", "digest", "source",
    "joined_at_rung", "frontier-peak", "peak_frontier", "chunks",
    "spill-rows", "spill-bytes", "device_bytes_peak", "queue_latency_s",
    "history_ops", "svg", "evidence",
    # confirm.resolved's mode (worker vs device-sweep) records which
    # confirm pool happened to be free, not what was decided.
    "mode",
})


def _register() -> None:
    from jepsen_tpu.store import durable

    durable.register_kind(KIND_BUNDLE, 1)


_register()


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------


def history_fingerprint(history) -> str:
    """The canonical content fingerprint of one history — the same
    sha256 the checkpoint layer keys resume-safety on."""
    from jepsen_tpu.store import checkpoint as _ckpt

    return _ckpt.fingerprint([history])


_MACHINE: dict | None = None


def machine_fingerprint() -> dict:
    """Host/toolchain fingerprint (cached; never probes a device
    backend — same convention as graftlint and the bench outage
    path)."""
    global _MACHINE
    if _MACHINE is None:
        try:
            from jepsen_tpu.obs import regress

            _MACHINE = dict(regress.fingerprint(probe_devices=False))
        except Exception:  # noqa: BLE001 — fingerprinting is best-effort
            _MACHINE = {"host": "unknown"}
    return dict(_MACHINE)


def verdict_str(v) -> str:
    """Canonical verdict string: True → "true", False → "false",
    anything else (UNKNOWN, None, "unknown") → "unknown"."""
    if v is True:
        return "true"
    if v is False:
        return "false"
    return "unknown"


# ---------------------------------------------------------------------------
# Decision-path attachment (pure dict plumbing; producers call this)
# ---------------------------------------------------------------------------


def attach(result: dict, path: Sequence[Mapping] | None = None, *,
           engine: Mapping | None = None,
           config: Mapping | None = None) -> dict:
    """Merge decision-path provenance into a result dict (in place).

    ``path`` entries are prepended before any entries already on the
    result (an outer ladder's events precede the chunked escalation's
    own trajectory).  ``engine``/``config`` fill only missing keys —
    the innermost producer knows its resolution best.  Idempotent for a
    fixed ``path`` list: callers re-attach freely at every notify
    point, the LAST attach before the result leaves the producer wins.
    """
    prov = result.get("provenance")
    existing = list(prov.get("path", ())) if isinstance(prov, Mapping) else []
    new = [dict(e) for e in (path or ())]
    # idempotence: drop the existing prefix if it is exactly a prior
    # attach of the same (possibly shorter) producer list
    if new and existing[: len(new)] == new:
        merged = existing
    else:
        seen = {json.dumps(e, sort_keys=True, default=str) for e in new}
        merged = new + [
            e for e in existing
            if json.dumps(e, sort_keys=True, default=str) not in seen
        ]
    if len(merged) > MAX_PATH:
        merged = merged[:MAX_PATH] + [
            {"event": "path.truncated", "dropped": len(merged) - MAX_PATH}
        ]
    out = {"path": merged}
    eng = dict(prov.get("engine", ())) if isinstance(prov, Mapping) else {}
    for k, v in (engine or {}).items():
        eng.setdefault(k, v)
    if eng:
        out["engine"] = eng
    cfg = dict(prov.get("config", ())) if isinstance(prov, Mapping) else {}
    for k, v in (config or {}).items():
        cfg.setdefault(k, v)
    if cfg:
        out["config"] = cfg
    result["provenance"] = out
    return result


class PathRecorder:
    """A bounded per-verdict decision-path accumulator.  ``add`` is
    cheap and never raises; ``entries`` hands the list to
    :func:`attach`."""

    __slots__ = ("entries",)

    def __init__(self):
        self.entries: list[dict] = []

    def add(self, event: str, **attrs) -> None:
        if len(self.entries) >= MAX_PATH:
            return
        e = {"event": str(event)}
        e.update(attrs)
        self.entries.append(e)


# ---------------------------------------------------------------------------
# Bundle construction
# ---------------------------------------------------------------------------


def _extract_witness(model, history, result: Mapping) -> dict | None:
    """The constructive payload that makes a verdict auditable.

    * valid (linearizable): re-run the host greedy walk recording the
      fired effective ops — a full linearization order verify can step.
    * refuted (linearizable): the barrier op the kernel killed on.
    * refuted (elle): the anomaly cycles (each step chains to the
      next; verify checks closure).
    """
    v = result.get("valid?")
    if v is False:
        if result.get("anomalies"):
            return {"type": "cycle", "anomalies": result["anomalies"]}
        if result.get("op") is not None:
            return {"type": "refutation", "op": result["op"]}
        return None
    if v is not True:
        return None
    if result.get("anomaly-types") is not None or model is None:
        return None  # elle valid: absence of cycles has no walk
    if history is None or len(history) > WITNESS_WALK_MAX_OPS:
        return None
    try:
        from jepsen_tpu.checker import wgl_cpu

        order: list[dict] = []
        ok = wgl_cpu.greedy_walk(model, history, record=order)
        if ok is True:
            return {"type": "linearization", "order": order}
    except Exception:  # noqa: BLE001 — witness extraction is best-effort
        logger.debug("witness extraction failed", exc_info=True)
    return None


def _strip(x):
    t = type(x)
    if t in _store._JSON_SCALARS:
        return x  # before the ``Mapping`` ABC check, which costs more
    if t is dict or isinstance(x, Mapping):
        return {
            str(k): _strip(v) for k, v in x.items()
            if str(k) not in _DIGEST_STRIP
        }
    if isinstance(x, (list, tuple)):
        return [_strip(v) for v in x]
    return x


def _stable_cause(cause) -> str | None:
    """Causes sometimes embed run-local paths ("resumable checkpoint:
    /tmp/..."); the digest keeps only the stable prefix."""
    if cause is None:
        return None
    return str(cause).split("; resumable checkpoint:", 1)[0]


def bundle_digest(payload: Mapping) -> str:
    """sha256 over the bundle's stability core — same history + same
    decision path ⇒ same digest, wherever it ran."""
    from jepsen_tpu.store import durable

    core = {
        "history_fingerprint": payload.get("history_fingerprint"),
        "verdict": payload.get("verdict"),
        "cause": _stable_cause(payload.get("cause")),
        "model": payload.get("model"),
        "checker": payload.get("checker"),
        "decision_path": _strip(payload.get("decision_path") or []),
        "engine": _strip(payload.get("engine") or {}),
        "config": _strip(payload.get("config") or {}),
        "witness": _strip(payload.get("witness") or {}),
    }
    return hashlib.sha256(durable.canonical_bytes(core)).hexdigest()


#: the parts of a bundle's build that ``times`` accumulates, in seconds:
#: the history fingerprint, the witness walk, the digest
BUILD_TIMES = ("fingerprint_s", "witness_s", "digest_s")


def build_bundle(*, history, result: Mapping, source: str,
                 model=None, checker: str | None = None,
                 trace_id=None, config: Mapping | None = None,
                 extra_path: Sequence[Mapping] | None = None,
                 bundle_id: str | None = None,
                 times: dict | None = None) -> dict:
    """Assemble one evidence-bundle payload (no I/O).

    ``result`` may carry a ``provenance`` block from :func:`attach`;
    ``extra_path`` entries (the serving layer's admission/fastpath/
    bisect events) are prepended before it.  The returned payload's
    ``digest`` field is the stability-core digest.  ``times``, a dict
    with the keys of :data:`BUILD_TIMES`, has this build's seconds of
    each part added to it.
    """
    t0 = time.perf_counter()
    fp = history_fingerprint(history) if history is not None else None
    t1 = time.perf_counter()
    witness = _extract_witness(model, history, result)
    t2 = time.perf_counter()
    prov = result.get("provenance") or {}
    path = [dict(e) for e in (extra_path or ())]
    path += [dict(e) for e in prov.get("path", ())]
    engine = dict(prov.get("engine", ()))
    cfg = dict(config or prov.get("config", ()))
    cfg.pop("fingerprint", None)  # batch-level; not per-history-stable
    v = result.get("valid?")
    payload = {
        "id": bundle_id or uuid.uuid4().hex[:16],
        "source": str(source),
        "model": getattr(model, "name", None) if model is not None else None,
        "checker": checker,
        "history_fingerprint": fp,
        "history_ops": len(history) if history is not None else None,
        "verdict": verdict_str(v),
        "cause": result.get("cause"),
        "decision_path": path,
        "engine": engine,
        "config": cfg,
        "witness": witness,
        "machine": machine_fingerprint(),
        "trace_id": trace_id,
    }
    if history is not None and len(history) <= MAX_EMBED_OPS:
        payload["history"] = [dict(op) for op in history]
    t3 = time.perf_counter()
    payload["digest"] = bundle_digest(payload)
    if times is not None:
        times["fingerprint_s"] += t1 - t0
        times["witness_s"] += t2 - t1
        times["digest_s"] += time.perf_counter() - t3
    return payload


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------


def write_bundle(directory, payload: Mapping) -> Path | None:
    """Durably persist one bundle as ``<dir>/<id>.json`` (enveloped:
    CRC + kind + version).  Best-effort: failures count
    ``provenance.emit_error`` and return None, never raise — an
    evidence write must not lose the verdict it documents."""
    from jepsen_tpu.store import durable

    try:
        d = Path(directory)
        d.mkdir(parents=True, exist_ok=True)
        path = d / f"{payload['id']}.json"
        durable.write_record(path, KIND_BUNDLE, payload)
        obs.counter("provenance.bundle", source=payload.get("source"),
                    verdict=payload.get("verdict"))
        return path
    except Exception as e:  # noqa: BLE001 — see docstring
        logger.warning("evidence bundle write failed: %s", e)
        obs.counter("provenance.emit_error", error=type(e).__name__)
        return None


def read_bundle(path) -> dict:
    """Read + verify one bundle envelope.  Raises
    ``store.durable.DurableError`` (machine-readable ``.report``) on a
    corrupt/tampered envelope — the file is quarantined aside."""
    from jepsen_tpu.store import durable

    return durable.read_verified(path, KIND_BUNDLE).payload


def iter_bundles(run_dir):
    """Yield ``(path, payload)`` for every readable bundle under a run
    directory's ``evidence/`` folder (corrupt ones are skipped with a
    warning — they are already quarantined aside)."""
    from jepsen_tpu.store import durable

    d = Path(run_dir)
    ev = d / "evidence" if (d / "evidence").is_dir() else d
    for p in sorted(ev.glob("*.json")):
        try:
            yield p, read_bundle(p)
        except durable.DurableError as e:
            logger.warning("skipping corrupt bundle %s: %s", p, e)


def emit(test: Mapping | None, history, result: dict, *, source: str,
         model=None, checker: str | None = None,
         config: Mapping | None = None, opts: Mapping | None = None,
         trace_id=None, times: dict | None = None) -> dict | None:
    """Checker-level emission: build a bundle for ``result`` and write
    it under the run's store dir (``<test-dir>/evidence/<id>.json``).
    Mirrors ``_render_failure``'s guard — a bare unit-test checker with
    no store coordinates records nothing (but the in-memory provenance
    stays on the result).  Sets ``result["evidence"] = {id, digest,
    path}`` on success; never raises.  ``times`` is
    :func:`build_bundle`'s."""
    try:
        bundle = build_bundle(
            history=history, result=result, source=source, model=model,
            checker=checker, config=config, trace_id=trace_id, times=times,
        )
    except Exception as e:  # noqa: BLE001 — provenance never loses verdicts
        logger.warning("evidence bundle build failed: %s", e)
        obs.counter("provenance.emit_error", error=type(e).__name__)
        return None
    from jepsen_tpu import store

    if not store.has_test_dir(test):
        return bundle  # no store configured (bare checker unit tests)
    d = store.test_dir(test)
    sub = (opts or {}).get("subdirectory")
    d = d / sub if sub else d
    path = write_bundle(d / "evidence", bundle)
    if path is not None:
        result["evidence"] = {
            "id": bundle["id"], "digest": bundle["digest"],
            "path": str(path),
        }
    return bundle


# ---------------------------------------------------------------------------
# Verify: structural audit + witness re-validation
# ---------------------------------------------------------------------------


def _check_linearization(model, history, order: Sequence[Mapping]) -> list[str]:
    """Re-step the model through a claimed linearization.  Checks (a)
    every step is consistent, and (b) the order fires exactly the
    effective ops ``prepare`` derives from the history — a forged or
    truncated walk fails one of the two."""
    from jepsen_tpu import models as m
    from jepsen_tpu.checker import wgl_cpu

    errors: list[str] = []
    state = model
    for n, op in enumerate(order):
        state = state.step(op)
        if m.is_inconsistent(state):
            errors.append(
                f"witness step {n} inconsistent: f={op.get('f')!r} "
                f"value={op.get('value')!r} ({state.msg})"
            )
            return errors
    _events, eff_ops, crashed = wgl_cpu.prepare(model, history)

    def _key(op):
        return (op.get("f"), json.dumps(op.get("value"), sort_keys=True,
                                        default=str))

    want: dict = {}
    want_ok: dict = {}
    got: dict = {}
    for i, op in eff_ops.items():
        want[_key(op)] = want.get(_key(op), 0) + 1
        if i not in crashed:
            want_ok[_key(op)] = want_ok.get(_key(op), 0) + 1
    for op in order:
        got[_key(op)] = got.get(_key(op), 0) + 1
    # Crashed ops may legitimately be absent (a linearization need not
    # fire an op that never definitely completed), but every ok op MUST
    # fire and nothing may fire more often than the history offers — a
    # forged or truncated walk fails one of the two bounds.
    for k, n in got.items():
        if n > want.get(k, 0):
            errors.append(f"witness fires op {k} {n}x but history has "
                          f"{want.get(k, 0)}")
    for k, n in want_ok.items():
        if got.get(k, 0) < n:
            errors.append(
                f"witness omits completed op {k} ({got.get(k, 0)} fired, "
                f"{n} required)"
            )
    return errors


def _check_cycle(anomalies: Mapping) -> list[str]:
    """A claimed cycle must actually cycle: every step's ``to`` is the
    next step's ``from`` and the last closes back to the first."""
    errors: list[str] = []
    for name, cycles in (anomalies or {}).items():
        for ci, c in enumerate(cycles or ()):
            steps = c.get("steps") or []
            if not steps:
                errors.append(f"anomaly {name}[{ci}]: no steps")
                continue
            for si, st in enumerate(steps):
                nxt = steps[(si + 1) % len(steps)]
                if st.get("to") != nxt.get("from"):
                    errors.append(
                        f"anomaly {name}[{ci}]: step {si} does not chain "
                        f"(to != next.from) — the claimed cycle does not "
                        "cycle"
                    )
                    break
            cyc = c.get("cycle")
            if cyc and len(cyc) != len(steps):
                errors.append(
                    f"anomaly {name}[{ci}]: {len(cyc)} ops vs "
                    f"{len(steps)} steps"
                )
    return errors


_REQUIRED = ("id", "source", "verdict", "history_fingerprint",
             "decision_path", "engine", "digest")


def verify_bundle(bundle, *, path=None) -> dict:
    """Structurally audit one bundle; returns a machine-readable report
    ``{"ok": bool, "checks": [...], "errors": [...]}``.  ``bundle`` is
    a payload dict or a path (then the envelope CRC is checked first
    and a tampered envelope fails with the durable layer's report)."""
    from jepsen_tpu import models as m
    from jepsen_tpu.store import durable

    checks: list[str] = []
    errors: list[str] = []
    report = {"ok": False, "checks": checks, "errors": errors}
    if not isinstance(bundle, Mapping):
        path = bundle
        try:
            bundle = read_bundle(path)
        except durable.DurableError as e:
            errors.append(f"envelope: {e}")
            report["envelope"] = e.report
            return report
        checks.append("envelope-crc")
    for k in _REQUIRED:
        if bundle.get(k) in (None, ""):
            errors.append(f"missing required field: {k}")
    if errors:
        return report
    checks.append("required-fields")
    if bundle_digest(bundle) != bundle["digest"]:
        errors.append("digest mismatch: stability core was altered after "
                      "the digest was computed")
        return report
    checks.append("digest")
    history = bundle.get("history")
    if history is not None:
        if history_fingerprint(history) != bundle["history_fingerprint"]:
            errors.append("history fingerprint mismatch: embedded history "
                          "was altered")
            return report
        checks.append("history-fingerprint")
    witness = bundle.get("witness")
    if witness:
        wt = witness.get("type")
        if wt == "linearization":
            if bundle.get("model") and history is not None:
                model = m.model(bundle["model"])
                errs = _check_linearization(
                    model, history, witness.get("order") or ())
                if errs:
                    errors.extend(errs)
                    return report
                checks.append("witness-linearization")
            else:
                checks.append("witness-linearization-skipped")
        elif wt == "cycle":
            errs = _check_cycle(witness.get("anomalies") or {})
            if errs:
                errors.extend(errs)
                return report
            checks.append("witness-cycle")
        elif wt == "refutation":
            op = witness.get("op")
            if history is not None and op is not None:
                fv = (op.get("f"), op.get("process"))
                if not any((o.get("f"), o.get("process")) == fv
                           for o in history):
                    errors.append("refutation op not present in history")
                    return report
            checks.append("witness-refutation")
    elif bundle["verdict"] == "false":
        errors.append("refuted verdict carries no witness payload")
        return report
    report["ok"] = True
    return report


# ---------------------------------------------------------------------------
# Replay: re-run pinned to the recorded engine/backend/config
# ---------------------------------------------------------------------------


def replay_bundle(bundle, *, deadline_zero_on_deadline_path: bool = True) -> dict:
    """Re-run the bundled history pinned to the recorded engine /
    backend / config and compare verdicts.  Returns ``{"ok", "verdict",
    "replayed", "pinned", "errors"}``; ``ok`` means verdict identity.

    A bundle whose decision path records a deadline trip replays under
    a zero budget (``faults.Deadline(0.0)``) so the degraded-unknown
    outcome is deterministic rather than racing the original timeout.
    """
    from jepsen_tpu import faults
    from jepsen_tpu import models as m
    from jepsen_tpu.store import durable

    errors: list[str] = []
    out = {"ok": False, "verdict": None, "replayed": None, "pinned": {},
           "errors": errors}
    if not isinstance(bundle, Mapping):
        try:
            bundle = read_bundle(bundle)
        except durable.DurableError as e:
            errors.append(f"envelope: {e}")
            out["envelope"] = e.report
            return out
    out["verdict"] = bundle.get("verdict")
    history = bundle.get("history")
    if history is None:
        errors.append("history not embedded (too large); replay needs the "
                      "original run artifacts")
        return out
    engine = bundle.get("engine") or {}
    cfg = bundle.get("config") or {}
    checker = bundle.get("checker") or ""
    path_events = {e.get("event") for e in bundle.get("decision_path") or ()}
    deadline = None
    if deadline_zero_on_deadline_path and any(
            str(ev).startswith("fault.deadline") for ev in path_events):
        deadline = faults.Deadline(0.0)
    out["pinned"] = {"engine": engine, "config": cfg,
                     "zero_deadline": deadline is not None}
    try:
        if checker.startswith("elle") or engine.get("engine") == "elle":
            replayed = _replay_elle(bundle, history, engine)
        else:
            model = m.model(bundle["model"]) if bundle.get("model") else None
            if model is None:
                errors.append("no model recorded; cannot replay")
                return out
            from jepsen_tpu.parallel.batch import batch_analysis, removed_path

            removed = removed_path(cfg)
            if removed is not None:
                errors.append(f"bundle pins a removed ladder path: {removed}")
                return out
            kw = {}
            if cfg.get("capacity"):
                kw["capacity"] = tuple(int(c) for c in cfg["capacity"])
            if cfg.get("exact_escalation") is not None:
                kw["exact_escalation"] = tuple(
                    int(c) for c in cfg["exact_escalation"])
            for k in ("rounds", "greedy_first", "confirm_refutations",
                      "frontier_budget_mb"):
                if cfg.get(k) is not None:
                    kw[k] = cfg[k]
            if engine.get("dedup_backend"):
                kw["dedup_backend"] = engine["dedup_backend"]
            replayed = batch_analysis(
                model, [history], cpu_fallback=deadline is None,
                deadline=deadline, **kw,
            )[0]
    except Exception as e:  # noqa: BLE001 — report, don't crash the audit
        errors.append(f"replay raised: {e!r}")
        return out
    out["replayed"] = verdict_str(replayed.get("valid?"))
    if out["replayed"] != bundle.get("verdict"):
        errors.append(
            f"verdict mismatch: bundle says {bundle.get('verdict')!r}, "
            f"replay under the pinned engine/config produced "
            f"{out['replayed']!r}"
        )
        return out
    out["ok"] = True
    return out


def _replay_elle(bundle: Mapping, history, engine: Mapping) -> dict:
    """Rebuild the recorded elle checker and re-check."""
    from jepsen_tpu.checker import elle

    checker = bundle.get("checker") or ""
    eng = engine.get("graph_engine") or engine.get("elle_engine")
    if "cycle" in checker:
        # CycleChecker wraps a user-supplied analyzer callable — not
        # serializable, so a cycle bundle can only be verified
        # (witness re-validation), not replayed.
        raise ValueError(
            "elle-cycle bundles record a user analyzer callable that "
            "cannot be reconstructed; use `evidence.py verify` instead"
        )
    if "wr-register" in checker:
        chk = elle.WRRegisterChecker(engine=eng)
    else:
        chk = elle.ListAppendChecker(engine=eng)
    return chk.check({}, history, {})
