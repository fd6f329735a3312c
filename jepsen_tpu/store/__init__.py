"""Persistence: where test runs live on disk.

Mirrors ``jepsen.store`` (reference: jepsen/src/jepsen/store.clj): each run
gets ``store/<name>/<timestamp>/`` (store.clj:33-68) holding the test map,
the history, the results, and downloaded node logs, with ``latest``
symlinks maintained at both levels (store.clj:282-319).  Writes happen in
three phases, exactly like the reference's crash-safety story
(store.clj:375-420, rationale in store/format.clj:141-150):

  save_0 — initial test map, before anything runs
  save_1 — the history, as soon as the run ends (pre-analysis: a crash in
           a checker must never lose the history)
  save_2 — the results

Formats: the test map and results are JSON (non-serializable values
stringified, mirroring store.clj:92-104's nonserializable-key stripping);
the history is JSON-lines (one op per line, like history.edn) plus the
human-readable ``history.txt``.  All writes go through tmp + fsync +
rename (+ directory fsync) so a crash never leaves a torn file and a
completed write survives a hard power cut — the same path the checker's
``checker-checkpoint.json``/``.npz`` ride (store/checkpoint.py).
"""

from __future__ import annotations

import contextlib as _contextlib
import datetime as _dt
import json
import logging
import os
import shutil
import tempfile
from pathlib import Path
from typing import Any, Mapping, Sequence

from jepsen_tpu import history as _h

logger = logging.getLogger(__name__)

BASE_DIR = Path("store")

#: Test-map keys that can't/shouldn't be serialized (functions, live
#: objects) — store.clj:92-104.
NONSERIALIZABLE_KEYS = (
    "db", "os", "net", "client", "nemesis", "checker", "generator", "remote",
    "sessions", "barrier", "store",
)


def base_dir(test_or_opts: Mapping | None = None) -> Path:
    if test_or_opts and test_or_opts.get("store-dir"):
        return Path(test_or_opts["store-dir"])
    return BASE_DIR


def time_str(t: _dt.datetime | None = None) -> str:
    """Directory-name timestamp (store.clj:45-50)."""
    t = t or _dt.datetime.now()
    return t.strftime("%Y%m%dT%H%M%S.%f")[:-3] + "Z"


def test_dir(test: Mapping) -> Path:
    return base_dir(test) / str(test["name"]) / str(test["start-time-str"])


def has_test_dir(test: Mapping | None) -> bool:
    """Whether ``test`` carries the store coordinates ``test_dir`` needs
    (bare checker unit tests and benchmark checks carry none)."""
    return bool(test and test.get("name") and test.get("start-time-str"))


#: the exact types JSON holds as they are.  Subclasses (``IntEnum``, a
#: ``str`` subclass) are not here: they take ``_jsonable``'s general
#: chain, whose output for them is the contract.
_JSON_SCALARS = frozenset({str, int, float, bool, type(None)})


def _jsonable(x: Any):
    # exact-type dispatch first: ``isinstance`` against the ``Mapping``
    # ABC costs more than the rest of the encoding for plain values, and
    # every store write, envelope CRC and history fingerprint comes here
    t = type(x)
    if t in _JSON_SCALARS:
        return x
    if t is dict:
        return {str(k): _jsonable(v) for k, v in x.items()}
    if t is list or t is tuple:
        return [_jsonable(v) for v in x]
    if isinstance(x, Mapping):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple, set, frozenset)):
        return [_jsonable(v) for v in x]
    if isinstance(x, _h.ColumnHistory):
        return [_jsonable(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    try:
        import numpy as np

        if isinstance(x, np.integer):
            return int(x)
        if isinstance(x, np.floating):
            return float(x)
        if isinstance(x, np.ndarray):
            return x.tolist()
    except ImportError:  # pragma: no cover
        pass
    return str(x)


def serializable_test(test: Mapping) -> dict:
    """The test map minus live objects (store.clj:92-104)."""
    return _jsonable({k: v for k, v in test.items() if k not in NONSERIALIZABLE_KEYS})


def _fsync_dir(d: Path) -> None:
    """fsync a directory so a just-renamed entry survives a power cut
    (rename atomicity alone only orders the rename against the crash,
    not against the disk).  Platforms without directory fds skip."""
    try:
        fd = os.open(d, os.O_RDONLY)
    except OSError:  # pragma: no cover - e.g. Windows
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover - fs without dir fsync
        pass
    finally:
        os.close(fd)


def _write_seam(step: str, path) -> None:
    """The crashpoint-audit seam: every ``_atomic_write`` step announces
    itself through ``faults.INJECT`` so ``tools/crashpoint.py`` can kill
    (SIGKILL in a child) or simulate a death (``faults.CrashPoint``) at
    exactly post-tmp / post-fsync / post-rename / pre-dir-fsync.
    Injectors MUST filter on ``ctx["what"] == "store.atomic_write"`` —
    a rate-based launch-fault schedule raising here would fault writes
    no retry policy covers (``faults.seeded_injector`` skips these
    seams unless explicitly targeted).  Lazy import: the store package
    stays importable without the faults layer resolved first."""
    from jepsen_tpu import faults

    hook = faults.INJECT
    if hook is not None:
        hook({"what": "store.atomic_write", "step": step,
              "path": str(path)}, 0)


def _atomic_write(path: Path, data: str | bytes):
    """tmp + fsync + rename + dir fsync: a reader never sees a torn
    file (rename atomicity), and a completed write survives a hard
    power cut (the data AND the directory entry are durable before the
    tmp name disappears).  Checkpoints and results both ride this.
    Torn-by-other-means (bit rot, hand edits, partial copies) is the
    durable-record layer's job — see ``store.durable``, whose checksums
    wrap every artifact that outlives a process.

    The tmp name is UNIQUE per writer (mkstemp), not ``<path>.tmp``:
    composed checkers write into one run dir concurrently, and two
    writers sharing a fixed tmp name could publish a torn mix of both.
    Concurrent same-path writers thus stay last-writer-wins, each write
    atomic.  (Crashed writers leave their unique ``*.tmp`` behind;
    ``durable.sweep_tmp`` reclaims them at store open / service start.)

    Each write step runs the ``faults.INJECT`` crashpoint seam
    (``_write_seam``).  A ``faults.CrashPoint`` raised there simulates
    the process dying at that step: NO cleanup runs, so the on-disk
    state is exactly what a SIGKILL at that instant leaves."""
    path = Path(path)
    fd, tmp = tempfile.mkstemp(
        dir=str(path.parent or "."), prefix=path.name + ".", suffix=".tmp"
    )
    binary = isinstance(data, (bytes, bytearray))
    try:
        with os.fdopen(fd, "wb" if binary else "w") as f:
            f.write(data)
            f.flush()
            _write_seam("post-tmp", path)
            os.fsync(f.fileno())
        _write_seam("post-fsync", path)
        os.chmod(tmp, 0o644)  # mkstemp's 0600 would hide artifacts from the web UI user
        os.replace(tmp, path)
    except BaseException as e:
        from jepsen_tpu import faults as _faults

        if not isinstance(e, _faults.CrashPoint):
            with _contextlib.suppress(OSError):
                os.unlink(tmp)
        raise
    _write_seam("post-rename", path)
    _write_seam("pre-dir-fsync", path)
    _fsync_dir(path.parent)


def _write_json(path: Path, obj):
    _atomic_write(path, json.dumps(_jsonable(obj), indent=1))


def write_history(d: Path, history: Sequence[Mapping]):
    """history.jsonl (machine) + history.txt (human) — store.clj:384-399
    writes both forms in parallel futures; sequential is fine here."""
    lines = [json.dumps(_jsonable(o), separators=(",", ":")) for o in history]
    _atomic_write(d / "history.jsonl", "\n".join(lines) + ("\n" if lines else ""))
    txt = []
    for o in history:
        txt.append(
            f"{o.get('index', ''):>8} {str(o.get('process', '')):>8} "
            f"{o.get('type', ''):<8} {str(o.get('f', '')):<16} {o.get('value', '')!r}"
        )
    _atomic_write(d / "history.txt", "\n".join(txt) + ("\n" if txt else ""))


def _run_file(d: Path) -> Path:
    return d / "run.jepsen"


def save_0(test: Mapping) -> Mapping:
    """Write the initial test map; returns test with paths filled
    (store.clj:375-382)."""
    from jepsen_tpu.store import format as fmt

    d = test_dir(test)
    d.mkdir(parents=True, exist_ok=True)
    # Store open is the sweep point for ``*.tmp`` orphans a crashed
    # writer left in this run dir (age-gated: a concurrently-writing
    # composed checker's live tmp survives).
    from jepsen_tpu.store import durable as _durable

    _durable.sweep_tmp(d, what="store")
    _write_json(d / "test.json", serializable_test(test))
    w = fmt.Writer(_run_file(d))
    w.write_test(test)
    update_symlinks(test)
    return test


def save_1(test: Mapping) -> Mapping:
    """Write the history immediately after the run — BEFORE analysis, so a
    crash in a checker can never lose it (store.clj:384-399)."""
    from jepsen_tpu.store import format as fmt

    d = test_dir(test)
    d.mkdir(parents=True, exist_ok=True)
    _write_json(d / "test.json", serializable_test(test))
    write_history(d, test.get("history") or [])
    w = fmt.Writer(_run_file(d))
    if not any(b["type"] == fmt.T_TEST for b in w.index["blocks"]):
        w.write_test(test)
    w.write_history(test.get("history") or [])
    return test


def save_2(test: Mapping) -> Mapping:
    """Write the results and seal the block file (store.clj:401-419)."""
    from jepsen_tpu.store import format as fmt

    d = test_dir(test)
    d.mkdir(parents=True, exist_ok=True)
    _write_json(d / "results.json", test.get("results") or {})
    w = fmt.Writer(_run_file(d))
    # A dir stored before the block format (or whose file was lost) gets a
    # complete file, not a results-only one that would shadow the JSON
    # artifacts in load_dir.
    if not any(b["type"] == fmt.T_TEST for b in w.index["blocks"]):
        w.write_test(test)
    if not any(b["type"] == fmt.T_HISTORY for b in w.index["blocks"]) and test.get("history"):
        w.write_history(test["history"])
    w.write_results(test.get("results") or {})
    w.close()
    update_symlinks(test)
    return test


def update_symlinks(test: Mapping):
    """Maintain <name>/latest and store/latest (store.clj:282-319)."""
    d = test_dir(test)
    for link in (d.parent / "latest", base_dir(test) / "latest"):
        try:
            if link.is_symlink() or link.exists():
                link.unlink()
            link.parent.mkdir(parents=True, exist_ok=True)
            link.symlink_to(os.path.relpath(d, link.parent))
        except OSError:  # pragma: no cover - symlinks may be unsupported
            logger.debug("couldn't update symlink %s", link, exc_info=True)


# ---------------------------------------------------------------------------
# Reading (store.clj:121-246)
# ---------------------------------------------------------------------------


def iter_runs(store_dir=None):
    """Yield ``(name, timestamp, run_dir, mtime_ns)`` for every stored
    run — the ONE store-directory enumeration (dir, non-symlink, two
    levels) that ``tests()`` and the web dashboard's cached run index
    both consume, so what counts as "a run" can never diverge between
    the API and the UI."""
    base = base_dir({"store-dir": store_dir} if store_dir else None)
    if not base.exists():
        return
    for name_dir in sorted(base.iterdir()):
        if not name_dir.is_dir() or name_dir.is_symlink():
            continue
        for run in sorted(name_dir.iterdir()):
            if not run.is_dir() or run.is_symlink():
                continue
            try:
                mt = run.stat().st_mtime_ns
            except OSError:
                continue
            yield name_dir.name, run.name, run, mt


def tests(name: str | None = None, store_dir=None) -> dict:
    """{name: {timestamp: path}} of stored runs (store.clj:121-160)."""
    out: dict = {}
    for n, ts, run, _mt in iter_runs(store_dir=store_dir):
        if name is not None and n != name:
            continue
        out.setdefault(n, {})[ts] = run
    return out


def load(name: str, timestamp: str, store_dir=None) -> dict:
    """Load a stored test (test map + history + results)
    (store.clj:196-246)."""
    base = base_dir({"store-dir": store_dir} if store_dir else None)
    d = base / name / timestamp
    return load_dir(d)


def load_dir(d: Path) -> dict:
    d = Path(d)
    run = d / "run.jepsen"
    if run.exists():
        from jepsen_tpu.store import format as fmt

        try:
            idx = fmt.read_index(run)
            test = fmt.read(run, index=idx, history=False)
            cols, fs, extras = fmt.read_columns(run, index=idx)
            if len(cols["index"]):
                # the zero-copy path: ops materialize lazily; kernels and
                # vectorized consumers read the columns directly
                test["history"] = _h.ColumnHistory(cols, fs, extras)
            test["dir"] = str(d)
            return test
        except fmt.CorruptFile:
            logger.warning("corrupt %s; falling back to JSON artifacts", run)
    test = json.loads((d / "test.json").read_text()) if (d / "test.json").exists() else {}
    hist_path = d / "history.jsonl"
    if hist_path.exists():
        test["history"] = [
            json.loads(line) for line in hist_path.read_text().splitlines() if line
        ]
    res_path = d / "results.json"
    if res_path.exists():
        test["results"] = json.loads(res_path.read_text())
    test["dir"] = str(d)
    return test


def peek_dir(d: Path) -> dict:
    """Cheap metadata read: name / start-time / valid? / op-count WITHOUT
    loading history or results — the block file footer when present
    (store/format.py read_index), else the small JSON artifacts.  This is
    what the web test table and `test-all` summaries use."""
    d = Path(d)
    run = d / "run.jepsen"
    if run.exists():
        from jepsen_tpu.store import format as fmt

        try:
            idx = fmt.read_index(run)
            return {
                "name": idx.get("name"),
                "start-time-str": idx.get("start-time"),
                "valid?": idx.get("valid?"),
                "op-count": idx.get("op-count"),
                "dir": str(d),
            }
        except fmt.CorruptFile:
            pass
    out: dict = {"dir": str(d)}
    try:
        t = json.loads((d / "test.json").read_text())
        out["name"] = t.get("name")
        out["start-time-str"] = t.get("start-time-str")
    except (OSError, ValueError):
        pass
    try:
        out["valid?"] = json.loads((d / "results.json").read_text()).get("valid?")
    except (OSError, ValueError):
        out.setdefault("valid?", None)
    return out


def latest(store_dir=None) -> dict | None:
    """The most recent run across all tests (store.clj:282-291)."""
    base = base_dir({"store-dir": store_dir} if store_dir else None)
    link = base / "latest"
    if link.exists():
        return load_dir(link.resolve())
    newest = None
    for name, runs in tests(store_dir=store_dir).items():
        for ts, path in runs.items():
            if newest is None or ts > newest[0]:
                newest = (ts, path)
    return load_dir(newest[1]) if newest else None


def delete(name: str | None = None, store_dir=None):
    """Delete stored runs (store.clj:248-266)."""
    base = base_dir({"store-dir": store_dir} if store_dir else None)
    target = base / name if name else base
    if target.exists():
        shutil.rmtree(target)


@_contextlib.contextmanager
def capture_logging(test: Mapping, filename: str = "jepsen.log"):
    """Tee the harness log to ``store/<name>/<time>/<filename>`` for the
    duration (reference: jepsen/src/jepsen/store.clj:436-464 — unilog
    writes the run's console log to jepsen.log so a stored run carries
    its own post-mortem record; jepsen.web serves it).

    The file captures INFO+ regardless of the console level; existing
    handlers keep their previous effective threshold so console output
    is unchanged.
    """
    d = test_dir(test)
    d.mkdir(parents=True, exist_ok=True)
    handler = logging.FileHandler(d / filename, encoding="utf-8")
    handler.setLevel(logging.INFO)
    handler.setFormatter(
        logging.Formatter("%(asctime)s %(levelname)-7s [%(name)s] %(message)s")
    )
    root = logging.getLogger()
    old_level = root.level
    bumped: list[tuple[logging.Handler, int]] = []
    if root.getEffectiveLevel() > logging.INFO:
        # Lower the root gate so INFO records reach our handler, but pin
        # the previous threshold onto the other handlers.
        for hh in root.handlers:
            if hh.level < root.getEffectiveLevel():
                bumped.append((hh, hh.level))
                hh.setLevel(root.getEffectiveLevel())
        root.setLevel(logging.INFO)
    root.addHandler(handler)
    try:
        yield d / filename
    finally:
        root.removeHandler(handler)
        handler.close()
        root.setLevel(old_level)
        for hh, lvl in bumped:
            hh.setLevel(lvl)
