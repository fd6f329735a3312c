"""Fault injection for the checker pipeline (jepsen_tpu.faults).

Drives the ladder through the ``faults.INJECT`` seam — synthetic
OOM/transient launch errors on chosen stages, dead confirmation pools,
expired deadlines, mid-ladder kills — and asserts the robustness
contract: every history resolves to either the clean-run verdict or an
``unknown`` with an attributable ``cause``; a checkpoint+resume cycle
reproduces the uninterrupted run's verdicts exactly.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))

from genhist import corrupt, valid_register_history  # noqa: E402

from jepsen_tpu import faults, obs  # noqa: E402
from jepsen_tpu import models as m  # noqa: E402
from jepsen_tpu.checker import wgl_cpu  # noqa: E402
from jepsen_tpu.parallel import batch as pb  # noqa: E402
from jepsen_tpu.store import checkpoint as ckpt  # noqa: E402


class FakeXlaRuntimeError(RuntimeError):
    """Name + RuntimeError lineage match the classifier's contract."""


_HIST_CACHE: dict = {}


def make_histories(n=5, ops=40, procs=5, seed0=900, info=0.3):
    """Deterministic mixed workload; cached (histories AND the sweep
    oracle's expectations) so repeated tests don't re-pay the sweeps."""
    key = (n, ops, procs, seed0, info)
    if key not in _HIST_CACHE:
        hists, expect = [], []
        for i in range(n):
            hist = valid_register_history(ops, procs, seed=seed0 + i, info_rate=info)
            if i % 2:
                hist = corrupt(hist, seed=i)
                expect.append(
                    wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"])
            else:
                expect.append(True)
            hists.append(hist)
        _HIST_CACHE[key] = (hists, expect)
    return _HIST_CACHE[key]


KW = dict(capacity=(16, 64, 512), cpu_fallback=False, exact_escalation=(),
          confirm_refutations=False)

_CLEAN_CACHE: dict = {}


def clean_run(key=(5, 40, 5, 900, 0.3)):
    """The uninterrupted-run baseline for the standard workload, computed
    once per process (the ladder is deterministic)."""
    if key not in _CLEAN_CACHE:
        hists, _ = make_histories(*key)
        _CLEAN_CACHE[key] = pb.batch_analysis(m.CASRegister(None), hists, **KW)
    return _CLEAN_CACHE[key]


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Keep injected-fault tests fast and deterministic."""
    monkeypatch.setenv("JEPSEN_TPU_RETRY_BASE_S", "0.001")
    monkeypatch.setenv("JEPSEN_TPU_RETRY_MAX_S", "0.002")
    yield
    faults.INJECT = None


# ---------------------------------------------------------------------------
# Error classification + retry policy units
# ---------------------------------------------------------------------------


def test_error_kind_classification():
    assert faults.error_kind(FakeXlaRuntimeError("RESOURCE_EXHAUSTED: hbm")) == "oom"
    assert faults.error_kind(FakeXlaRuntimeError("INTERNAL: scheduler")) == "transient"
    assert faults.error_kind(
        RuntimeError("TPU worker process crashed or restarted")) == "transient"
    assert faults.error_kind(ConnectionResetError("connection reset")) == "transient"
    # not device faults: never retried/degraded silently
    assert faults.error_kind(ValueError("INTERNAL looking but wrong type")) is None
    assert faults.error_kind(RuntimeError("some other bug")) is None


@pytest.mark.parametrize("msg", [
    "INTERNAL: Mosaic failed to compile TPU kernel: bad slice",
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm",
    "Unable to initialize backend 'tpu': INTERNAL: Failed to get global "
    "TPU topology",
])
def test_compile_refusal_or_missing_backend_fails_the_run(msg):
    """A program the compiler refuses, or a backend that is not there,
    fails the same way on every retry and batch size: it is never
    retried, halved or degraded to unknown — the ladder raises."""
    calls = []

    def inject(ctx, attempt):
        if ctx.get("engine") in ("sync", "async"):
            calls.append(attempt)
            raise FakeXlaRuntimeError(msg)

    assert faults.error_kind(FakeXlaRuntimeError(msg)) is None
    hists, _ = make_histories()
    faults.INJECT = inject
    try:
        with pytest.raises(FakeXlaRuntimeError):
            pb.batch_analysis(m.CASRegister(None), hists, **KW)
    finally:
        faults.INJECT = None
    assert calls == [0]


def test_fired_lists_only_faults_with_counts():
    assert faults.fired(None) == []
    assert faults.fired({"faults": [
        {"fault": "launch.retry", "count": 2},
        {"fault": "deadline.trip", "count": 0},
    ]}) == ["fault.launch.retry x2"]


def test_fired_live_counts_faults_without_a_recording():
    """Warm-up runs and runs with telemetry off count too."""
    assert obs.active() is None
    before = obs.fault_tally().get("fault.test.unrecorded", 0)
    obs.counter("fault.test.unrecorded", 2)
    obs.counter("ladder.test.not_a_fault")
    assert obs.fault_tally()["fault.test.unrecorded"] == before + 2
    assert "ladder.test.not_a_fault" not in obs.fault_tally()
    assert f"fault.test.unrecorded x{before + 2}" in faults.fired_live()


def test_compile_failure_fails_the_run_whatever_its_message(monkeypatch):
    """An error raised while compiling a runner is fatal even when its
    text reads as transient: the ladder raises CompileError at once —
    no retry, no halving, no lane degraded to unknown."""
    import jax

    def refuse(self, *args, **kwargs):
        raise FakeXlaRuntimeError("INTERNAL: synthetic compiler refusal")

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    hists, _ = make_histories()
    before = faults.fired_live()
    with pytest.raises(faults.CompileError, match="synthetic"):
        # a capacity no other test compiles, so its runner is new here
        pb.batch_analysis(m.CASRegister(None), hists,
                          **{**KW, "capacity": (24,)})
    assert faults.fired_live() == before


@pytest.mark.parametrize("wrap", ["direct", "independent"])
def test_compile_failure_escapes_the_checker_catch_alls(wrap):
    """check_safe turns checker exceptions into unknown, and the
    independent checker falls back from its batch path to per-key
    checks; neither may swallow a compiler refusal."""
    from jepsen_tpu import checker, independent

    class Refusing(checker.Checker):
        def check(self, test, history, opts):
            raise faults.CompileError("refused")

        def check_batch(self, test, histories, opts):
            raise faults.CompileError("refused")

    chk = Refusing() if wrap == "direct" else independent.checker(Refusing())
    hist = [{"index": 0, "type": "invoke", "f": "write", "process": 0,
             "value": independent.tuple_(0, 1)},
            {"index": 1, "type": "ok", "f": "write", "process": 0,
             "value": independent.tuple_(0, 1)}]
    with pytest.raises(faults.CompileError):
        checker.check_safe(chk, {}, hist)


def test_call_with_retry_backs_off_then_succeeds():
    calls = []
    sleeps = []

    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise FakeXlaRuntimeError("UNAVAILABLE: transient runtime hiccup")
        return "ok"

    out = faults.call_with_retry(
        fn, {"what": "t"}, retries=3, base_s=0.5, max_s=8.0,
        sleep=sleeps.append,
    )
    assert out == "ok" and len(calls) == 3
    assert sleeps == [0.5, 1.0]  # exponential


def test_call_with_retry_oom_raises_immediately():
    calls = []

    def fn():
        calls.append(1)
        raise FakeXlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(faults.LaunchFailure) as ei:
        faults.call_with_retry(fn, retries=5, base_s=0, max_s=0)
    assert ei.value.kind == "oom" and len(calls) == 1


def test_call_with_retry_exhausts_then_launchfailure():
    def fn():
        raise FakeXlaRuntimeError("ABORTED: preempted")

    with pytest.raises(faults.LaunchFailure) as ei:
        faults.call_with_retry(fn, retries=2, base_s=0, max_s=0)
    assert ei.value.kind == "transient"
    assert "ABORTED" in str(ei.value)


def test_call_with_retry_reraises_foreign_errors():
    def fn():
        raise ValueError("a real bug")

    with pytest.raises(ValueError):
        faults.call_with_retry(fn, retries=5, base_s=0, max_s=0)


# ---------------------------------------------------------------------------
# Ladder under injected launch faults
# ---------------------------------------------------------------------------


def test_transient_fault_retried_verdicts_unchanged(tmp_path):
    hists, expect = make_histories()
    clean = clean_run()
    assert [r["valid?"] for r in clean] == expect

    hits = []

    def inject(ctx, attempt):
        # the first attempt of every stage-1 launch fails transiently
        if ctx.get("stage") == 1 and attempt < 1:
            hits.append(attempt)
            raise FakeXlaRuntimeError("INTERNAL: transient scheduler error")

    faults.INJECT = inject
    try:
        with obs.recording(tmp_path):
            res = pb.batch_analysis(m.CASRegister(None), hists, **KW)
    finally:
        faults.INJECT = None
    assert hits, "injector never fired"
    assert [r["valid?"] for r in res] == [r["valid?"] for r in clean]
    summary = json.loads((tmp_path / "telemetry.json").read_text())
    table = {f["fault"]: f for f in summary["faults"]}
    assert table["launch.retry"]["count"] >= 1


def test_oom_halves_sub_batch_verdicts_unchanged(tmp_path):
    hists, expect = make_histories()
    clean = clean_run()

    def inject(ctx, attempt):
        if ctx.get("engine") in ("sync", "async") and ctx.get("lanes", 0) > 1:
            raise FakeXlaRuntimeError("RESOURCE_EXHAUSTED: ran out of hbm")

    faults.INJECT = inject
    try:
        with obs.recording(tmp_path):
            res = pb.batch_analysis(m.CASRegister(None), hists, **KW)
    finally:
        faults.INJECT = None
    assert [r["valid?"] for r in res] == [r["valid?"] for r in clean]
    summary = json.loads((tmp_path / "telemetry.json").read_text())
    table = {f["fault"]: f for f in summary["faults"]}
    assert table["launch.oom_halving"]["count"] >= 1


def test_persistent_fault_degrades_only_its_lanes(monkeypatch, tmp_path):
    """A launch that still fails after retries costs exactly its own
    lanes — unknown with the error named — never the batch."""
    monkeypatch.setenv("JEPSEN_TPU_LAUNCH_RETRIES", "1")
    hists, expect = make_histories()

    def inject(ctx, attempt):
        if ctx.get("engine") in ("sync", "async"):
            raise FakeXlaRuntimeError("UNAVAILABLE: chip is gone")

    faults.INJECT = inject
    try:
        with obs.recording(tmp_path):
            res = pb.batch_analysis(m.CASRegister(None), hists, **KW)
    finally:
        faults.INJECT = None
    assert len(res) == len(hists)
    for r, want in zip(res, expect):
        # greedy (uninjected) may still resolve valid lanes; everything
        # else degrades attributably — never a wrong verdict, no crash
        assert r["valid?"] in (want, "unknown")
        if r["valid?"] == "unknown":
            assert "device launch failed" in r["cause"]
            assert "UNAVAILABLE" in r["cause"]
    assert any(r["valid?"] == "unknown" for r in res)
    summary = json.loads((tmp_path / "telemetry.json").read_text())
    table = {f["fault"]: f for f in summary["faults"]}
    assert table["launch.degraded"]["count"] >= 1


def test_chunked_analysis_degrades_on_persistent_fault(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_LAUNCH_RETRIES", "0")
    from jepsen_tpu.ops import wgl

    hist = valid_register_history(30, 3, seed=5, info_rate=0.2)

    def inject(ctx, attempt):
        if ctx.get("what") == "wgl.chunk":
            raise FakeXlaRuntimeError("INTERNAL: kernel fault")

    faults.INJECT = inject
    try:
        r = wgl.analysis(m.CASRegister(None), hist, capacity=(64,))
    finally:
        faults.INJECT = None
    assert r["valid?"] == "unknown"
    assert "device launch failed" in r["cause"]


def test_chunked_analysis_deadline():
    from jepsen_tpu.ops import wgl

    hist = valid_register_history(30, 3, seed=6, info_rate=0.2)
    r = wgl.analysis(
        m.CASRegister(None), hist, capacity=(64,), deadline=faults.Deadline(0.0)
    )
    assert r["valid?"] == "unknown"
    assert "deadline-exceeded" in r["cause"]


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    resumes = {
        3: (7, np.arange(4, dtype=np.int32), np.ones((4, 1), np.uint32),
            np.zeros((4, 2), np.int16), np.array([True, False, True, False])),
    }
    ckpt.save(
        tmp_path,
        config={"engine": "async", "capacity": [16, 64], "fingerprint": "fp"},
        stage=2,
        results={0: {"valid?": True}, 1: {"valid?": "unknown", "cause": "x"}},
        pending=[3],
        confirms={2: {"res": {"valid?": False}, "op_pos": 9}},
        resumes=resumes,
    )
    out = ckpt.load(tmp_path)
    assert out["stage"] == 2 and not out["complete"]
    assert out["results"][0]["valid?"] is True
    assert out["pending"] == [3]
    assert out["confirms"][2]["op_pos"] == 9
    bs, st, fo, fc, al = out["resumes"][3]
    assert bs == 7 and st.tolist() == [0, 1, 2, 3]
    assert al.tolist() == [True, False, True, False]


def test_kill_mid_ladder_then_resume_identical(tmp_path):
    """The in-process analogue of kill -9 between stage boundaries: a
    non-Exception interrupt aborts the run after stage 1's checkpoint;
    the resumed run's verdicts must equal the uninterrupted run's."""
    hists, expect = make_histories(5, ops=50, procs=6, seed0=950, info=0.35)
    kw = dict(capacity=(16, 256), cpu_fallback=False, exact_escalation=(),
              confirm_refutations=False)
    clean = pb.batch_analysis(m.CASRegister(None), hists, **kw)

    class Killed(BaseException):
        """Not an Exception: nothing in the pipeline may swallow it."""

    def inject(ctx, attempt):
        if ctx.get("stage", 0) >= 2:
            raise Killed()

    faults.INJECT = inject
    try:
        with pytest.raises(Killed):
            pb.batch_analysis(
                m.CASRegister(None), hists, checkpoint_dir=tmp_path, **kw
            )
    finally:
        faults.INJECT = None
    saved = ckpt.load(tmp_path)
    assert saved["stage"] >= 1 and not saved["complete"]

    resumed = pb.batch_analysis(
        m.CASRegister(None), hists, checkpoint_dir=tmp_path, resume=True, **kw
    )
    assert [r["valid?"] for r in resumed] == [r["valid?"] for r in clean]
    # and the resumed run sealed a complete checkpoint: resuming again is
    # idempotent (saved verdicts, no device work)
    assert ckpt.load(tmp_path)["complete"]
    again = pb.batch_analysis(
        m.CASRegister(None), hists, checkpoint_dir=tmp_path, resume=True, **kw
    )
    assert [r["valid?"] for r in again] == [r["valid?"] for r in clean]


def test_resume_config_overrides_caller_args(tmp_path):
    """On resume the SAVED ladder config wins (verdict identity needs the
    original ladder; the CLI resume path can't know the original kwargs)."""
    hists, expect = make_histories()
    kw = dict(KW)
    # interrupt at stage 0 so the resume has real ladder work left
    pb.batch_analysis(
        m.CASRegister(None), hists, checkpoint_dir=tmp_path,
        deadline=faults.Deadline(0.0), **kw,
    )
    saved = ckpt.load(tmp_path)
    assert saved["config"]["capacity"] == list(KW["capacity"])
    assert saved["pending"] and not saved["complete"]
    # resume with a DIFFERENT (useless) capacity arg: the checkpoint's
    # config wins, so the original ladder still resolves everything
    res = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(4,), cpu_fallback=False,
        exact_escalation=(), confirm_refutations=False,
        checkpoint_dir=tmp_path, resume=True,
    )
    assert [r["valid?"] for r in res] == expect


def test_checkpoint_fingerprint_mismatch_runs_fresh(tmp_path):
    hists_a, _ = make_histories()
    hists_b, expect_b = make_histories(2, seed0=2000)
    pb.batch_analysis(m.CASRegister(None), hists_a, checkpoint_dir=tmp_path, **KW)
    # resuming with different histories must ignore the checkpoint (a
    # wrong resume could only produce wrong verdicts) and run fresh
    res = pb.batch_analysis(
        m.CASRegister(None), hists_b, checkpoint_dir=tmp_path, resume=True, **KW
    )
    assert [r["valid?"] for r in res] == expect_b


@pytest.mark.parametrize("removed", [
    {"config": {"engine": "sync"}},
    {"config": {"confirm_refutations": "device"}},
    {"config": {"carry_frontier": False}},
    {"device_confirms": [
        {"i": 1, "failed_at": 3, "cap": 16, "res": {"valid?": False}}]},
], ids=["engine-sync", "confirm-device", "scratch-restart",
        "device-confirms"])
def test_checkpoint_naming_removed_path_runs_fresh(tmp_path, removed):
    """A checkpoint that pins a removed ladder path (the barrier-scan
    engine, device-side confirmation, scratch-restart rungs, or queued
    device confirmations) is quarantined on resume and the run starts
    fresh — never continued under the remaining ladder."""
    from jepsen_tpu.store import durable

    hists, _ = make_histories()
    # a resumable checkpoint with ladder work left (deadline trips at
    # stage 0), rewritten to name the removed path
    pb.batch_analysis(m.CASRegister(None), hists, checkpoint_dir=tmp_path,
                      deadline=faults.Deadline(0.0), **KW)
    jp = ckpt.json_path(tmp_path)
    rr = durable.read_verified(jp, ckpt.KIND_LADDER)
    doc = dict(rr.payload)
    doc["config"] = {**doc["config"], **removed.get("config", {})}
    doc.update({k: v for k, v in removed.items() if k != "config"})
    durable.write_record(jp, ckpt.KIND_LADDER, doc, files=rr.files)

    res = pb.batch_analysis(
        m.CASRegister(None), hists, checkpoint_dir=tmp_path, resume=True, **KW
    )
    assert list(tmp_path.glob(f"{ckpt.CKPT_JSON}.corrupt-*")), (
        sorted(p.name for p in tmp_path.iterdir()))
    for r in res:
        events = [e["event"] for e in r["provenance"]["path"]]
        assert "checkpoint.restored" not in events, events
    assert [r["valid?"] for r in res] == [r["valid?"] for r in clean_run()]
    # the fresh run sealed a main-path checkpoint of its own
    saved = ckpt.load(tmp_path)
    assert saved["complete"] and pb.removed_path(saved["config"]) is None


# ---------------------------------------------------------------------------
# Deadline-bounded degradation
# ---------------------------------------------------------------------------


def test_deadline_expiry_checkpoints_and_degrades(tmp_path):
    hists, expect = make_histories()
    with obs.recording(tmp_path / "tele"):
        res = pb.batch_analysis(
            m.CASRegister(None), hists, checkpoint_dir=tmp_path,
            deadline=faults.Deadline(0.0), **KW,
        )
    assert len(res) == len(hists)  # ALWAYS a complete result list
    for r in res:
        assert r["valid?"] == "unknown"
        assert "deadline-exceeded" in r["cause"]
        assert "checker-checkpoint.json" in r["cause"]  # pointer to resume
    # the trip checkpoint is loadable and resumable: a later run with no
    # deadline finishes the work with clean verdicts
    saved = ckpt.load(tmp_path)
    assert saved["pending"] and not saved["complete"]
    resumed = pb.batch_analysis(
        m.CASRegister(None), hists, checkpoint_dir=tmp_path, resume=True, **KW
    )
    assert [r["valid?"] for r in resumed] == expect
    summary = json.loads((tmp_path / "tele" / "telemetry.json").read_text())
    table = {f["fault"]: f for f in summary["faults"]}
    assert table["deadline.trip"]["count"] >= 1
    assert table["checkpoint.save"]["count"] >= 1


def test_deadline_threads_through_check_safe_and_compose(tmp_path):
    """The opts key rides check_safe/Compose into the checker: one shared
    budget, attributable unknowns, and analyze-style complete results."""
    from jepsen_tpu import checker as chk
    from jepsen_tpu.checker.linearizable import linearizable

    hist = valid_register_history(30, 3, seed=11, info_rate=0.2)
    composed = chk.compose({
        "stats": chk.stats(),
        "linear": linearizable(
            {"model": m.CASRegister(None), "algorithm": "competition"}
        ),
    })
    opts = chk.resolve_opts({"check-deadline": 1e-9})
    assert isinstance(opts["deadline"], faults.Deadline)
    res = chk.check_safe(composed, {"name": "t"}, hist, {"check-deadline": 1e-9})
    # stats (no device work) still reports; the linearizable checker
    # degrades attributably instead of running past the budget
    assert res["stats"]["valid?"] in (True, False)
    assert res["linear"]["valid?"] == "unknown"
    assert "deadline-exceeded" in res["linear"]["cause"]


# ---------------------------------------------------------------------------
# Confirmation-pool fault tolerance
# ---------------------------------------------------------------------------


def test_broken_pool_confirmation_resubmitted_once(monkeypatch, tmp_path):
    """An in-flight confirmation that dies with its pool is resubmitted
    once against the rebuilt pool — the verdict survives instead of
    degrading to unknown (and the retry lands in telemetry)."""
    from concurrent.futures.process import BrokenProcessPool

    from jepsen_tpu import _confirm_worker as cw

    hists, expect = make_histories()
    assert False in expect

    class ExplodingFuture:
        def result(self, timeout=None):
            raise BrokenProcessPool("worker died mid-sweep")

    class GoodFuture:
        def __init__(self, res):
            self._res = res

        def result(self, timeout=None):
            return self._res

    class ExplodingPool:
        def submit(self, fn, *a, **kw):
            return ExplodingFuture()

    class GoodPool:
        def submit(self, fn, *a, **kw):
            assert fn is cw.confirm_refutation
            return GoodFuture(cw.confirm_refutation(*a, **kw))

    pools = [ExplodingPool(), GoodPool()]
    state = {"n": 0}

    def fake_pool(workers):
        return pools[min(state["n"], 1)]

    def fake_reset():
        state["n"] += 1

    monkeypatch.setattr(pb, "_CONFIRM_POOL", pools[0])
    monkeypatch.setattr(pb, "_confirm_pool", fake_pool)
    monkeypatch.setattr(pb, "_reset_confirm_pool", fake_reset)
    with obs.recording(tmp_path):
        res = pb.batch_analysis(
            m.CASRegister(None), hists, capacity=(64, 256),
            cpu_fallback=False, exact_escalation=(),
        )
    # the resubmit rescued every refutation: verdicts match the oracle
    assert [r["valid?"] for r in res] == expect
    summary = json.loads((tmp_path / "telemetry.json").read_text())
    table = {f["fault"]: f for f in summary["faults"]}
    assert table["confirm.resubmit"]["count"] >= 1


# ---------------------------------------------------------------------------
# Satellites: fsync'd atomic writes, await_tcp_port backoff
# ---------------------------------------------------------------------------


def test_atomic_write_fsyncs_file_and_dir(tmp_path, monkeypatch):
    from jepsen_tpu import store

    synced = []
    real_fsync = pathlib.os.fsync
    monkeypatch.setattr(store.os, "fsync", lambda fd: synced.append(fd) or real_fsync(fd))
    p = tmp_path / "results.json"
    store._atomic_write(p, '{"ok": 1}')
    assert p.read_text() == '{"ok": 1}'
    assert len(synced) >= 2  # the temp file AND the directory
    # bytes payloads (the checkpoint npz) ride the same path
    store._atomic_write(tmp_path / "blob.npz", b"\x00\x01")
    assert (tmp_path / "blob.npz").read_bytes() == b"\x00\x01"
    assert not list(tmp_path.glob("*.tmp"))


def test_chaos_check_smoke():
    """tools/chaos_check.py's tier-1 smoke variant: one randomized
    injected-fault run plus the SIGKILL/resume differential on a tiny
    pinned workload — verdict agreement or attributable unknowns, and
    resume-identity after a real kill -9."""
    import chaos_check

    assert chaos_check.main(["--smoke"]) == 0


def test_await_tcp_port_backoff_and_last_error(monkeypatch):
    from jepsen_tpu.control import util as cu
    from jepsen_tpu.control.core import RemoteError

    class TransportDown(RemoteError):
        pass

    class FakeSession:
        node = "n1"

        def exec_result(self, *a, timeout=None):
            raise TransportDown("ssh transport is down")

    sleeps = []
    monkeypatch.setattr(cu.time, "sleep", sleeps.append)
    with pytest.raises(TimeoutError) as ei:
        cu.await_tcp_port(FakeSession(), 4444, timeout=0.05, interval=0.001,
                          max_interval=0.008)
    msg = str(ei.value)
    assert "n1:4444" in msg
    assert "ssh transport is down" in msg  # the last probe error is named
    assert len(sleeps) >= 3
    # exponential growth with jitter in [0.5, 1.0]x: later sleeps
    # dominate earlier ones, and none exceeds the cap
    assert max(sleeps) > 0.002
    assert max(sleeps) <= 0.008
