"""Batched + mesh-sharded checking tests, on the virtual 8-device CPU mesh
(the way the driver's dryrun validates multi-chip compilation)."""

import pathlib
import random
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))

from genhist import corrupt, valid_register_history
from jepsen_tpu import models as m
from jepsen_tpu.checker import wgl_cpu
from jepsen_tpu.parallel import batch_analysis, make_mesh


def histories_mixed(n=12):
    hists, expect = [], []
    for i in range(n):
        hist = valid_register_history(30, 3, seed=i, info_rate=0.1)
        if i % 3 == 2:
            hist = corrupt(hist, seed=i)
            expect.append(wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"])
        else:
            expect.append(True)
        hists.append(hist)
    return hists, expect


def test_batch_analysis_no_mesh():
    hists, expect = histories_mixed(9)
    results = batch_analysis(m.CASRegister(None), hists, capacity=(64, 256))
    assert [r["valid?"] for r in results] == expect


def test_batch_analysis_sharded_mesh():
    assert len(jax.devices()) == 8, "conftest should provide 8 virtual devices"
    mesh = make_mesh()
    hists, expect = histories_mixed(12)
    results = batch_analysis(m.CASRegister(None), hists, capacity=(64, 256), mesh=mesh)
    assert [r["valid?"] for r in results] == expect


def test_batch_handles_trivial_and_untensorizable():
    from jepsen_tpu import history as h

    hists = [
        [],  # no barriers -> trivially valid
        [h.op(h.INVOKE, 0, "write", 1), h.op(h.OK, 0, "write", 1)],
    ]
    results = batch_analysis(m.CASRegister(None), hists)
    assert results[0]["valid?"] is True
    assert results[1]["valid?"] is True
    fifo = batch_analysis(
        m.FIFOQueue(),
        [[h.op(h.INVOKE, 0, "enqueue", 1), h.op(h.OK, 0, "enqueue", 1)]],
        cpu_fallback=True,
    )
    assert fifo[0]["valid?"] is True  # fell back to CPU oracle


def test_linearizable_check_batch_via_independent():
    """independent.checker routes per-key register subhistories through
    the linearizable checker's batch path (one vmapped ladder)."""
    import pathlib, sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))
    from genhist import corrupt, valid_register_history

    from jepsen_tpu import history as h
    from jepsen_tpu import independent
    from jepsen_tpu import models as m
    from jepsen_tpu.checker.linearizable import linearizable

    hist = []
    t = 0
    for k in range(4):
        sub = valid_register_history(16, 2, seed=k, info_rate=0.1)
        if k == 2:
            sub = corrupt(sub, seed=k)
        for o in sub:
            o = dict(o)
            o["value"] = independent.tuple_(k, o["value"])
            o["time"] = (t := t + 1)
            hist.append(o)
    hist = h.index(hist)

    chk = independent.checker(linearizable({"model": m.CASRegister(None), "algorithm": "competition"}))
    res = chk.check({"name": "t"}, hist, {})
    assert res["results"][0]["valid?"] is True
    assert res["results"][2]["valid?"] is False
    assert res["valid?"] is False
    assert res["failures"] == [2]


def test_independent_batch_failure_is_counted(tmp_path):
    """A batch-capable checker that raises falls back to the per-key
    path — and says so in a fault counter, so a run that lost its
    batched launch cannot pass for one that had it."""
    import json

    from jepsen_tpu import independent, obs
    from jepsen_tpu.checker import Checker

    class BatchBroken(Checker):
        def check(self, test, history, opts):
            return {"valid?": True}

        def check_batch(self, test, histories, opts):
            raise RuntimeError("compile refused")

    hist = [{**o, "value": independent.tuple_(0, o["value"])}
            for o in valid_register_history(8, 2, seed=1, info_rate=0.0)]
    with obs.recording(tmp_path):
        res = independent.checker(BatchBroken()).check({}, hist, {})
    assert res["valid?"] is True
    counters = json.loads((tmp_path / "telemetry.json").read_text())["counters"]
    assert counters.get("fault.batch.fallback") == 1


def test_confirm_worker_isolated_from_accelerator(monkeypatch):
    """Round-3 regression: spawned confirmation workers initialized the
    accelerator backend and died (BrokenProcessPool, libtpu mismatch).
    The worker entry points live in the import-light jepsen_tpu._confirm_worker
    module, and its initializer pins jax to CPU (env var and config flag)
    while the parent keeps the chip."""
    from jepsen_tpu import _confirm_worker as cw
    from jepsen_tpu.parallel import batch as pb

    # Poison the inherited environment: point any env-var-honoring backend
    # selection at a TPU that does not exist here.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    pb._reset_confirm_pool()
    try:
        pool = pb._confirm_pool(2)
        hist = corrupt(valid_register_history(20, 2, seed=3, info_rate=0.2), seed=3)
        r = pool.submit(
            cw.confirm_refutation, m.CASRegister(None), hist, 100_000
        ).result(timeout=180)
        assert r["valid?"] in (True, False)
        info = pool.submit(cw.probe_backend).result(timeout=180)
        # The config flag won: the worker's backend is CPU despite the env.
        assert info["platform"] == "cpu"
        # The confirmation path stayed import-light: no kernel modules, no
        # parallel.batch (whose import would drag in both jax and the kernels).
        heavy = {"jepsen_tpu.ops.wgl", "jepsen_tpu.ops.hashing",
                 "jepsen_tpu.parallel.batch", "jepsen_tpu.models.tensor"}
        assert not heavy & set(info["jepsen_tpu_modules"]), info
    finally:
        pb._reset_confirm_pool()


def test_warm_confirm_pool_raises_when_workers_do_not_come_up(monkeypatch):
    """A pool that does not come up fails the warm-up loudly (and is
    dropped for rebuild): a run whose refutations cannot be confirmed
    must not pass for a healthy one."""
    from concurrent.futures.process import BrokenProcessPool

    import pytest

    from jepsen_tpu.parallel import batch as pb

    class ExplodingFuture:
        def result(self, timeout=None):
            raise BrokenProcessPool("worker died")

    class ExplodingPool:
        def submit(self, fn, *a, **kw):
            return ExplodingFuture()

    reset_calls = []
    monkeypatch.setattr(pb, "_confirm_pool", lambda workers: ExplodingPool())
    monkeypatch.setattr(pb, "_reset_confirm_pool", lambda: reset_calls.append(1))
    with pytest.raises(BrokenProcessPool):
        pb.warm_confirm_pool(2)
    assert reset_calls


def test_confirm_future_failure_degrades_to_unknown(monkeypatch):
    """A dead confirmation worker must cost one history's verdict, not the
    whole batch (advisor r3: unguarded fut.result() lost everything and
    left a broken module-global pool behind)."""
    from concurrent.futures.process import BrokenProcessPool

    from jepsen_tpu.parallel import batch as pb

    class ExplodingFuture:
        def result(self, timeout=None):
            raise BrokenProcessPool("worker died")

    class ExplodingPool:
        def submit(self, fn, *a, **kw):
            return ExplodingFuture()

    reset_calls = []
    pool = ExplodingPool()
    monkeypatch.setattr(pb, "_CONFIRM_POOL", pool)
    monkeypatch.setattr(pb, "_confirm_pool", lambda workers: pool)
    monkeypatch.setattr(pb, "_reset_confirm_pool", lambda: reset_calls.append(1))
    hists, expect = histories_mixed(6)
    results = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(64, 256), cpu_fallback=False,
        exact_escalation=(),
    )
    for r, want in zip(results, expect):
        if want is True:
            assert r["valid?"] is True  # valid verdicts survive
        else:
            assert r["valid?"] == "unknown"
            assert "confirmation worker failed" in r["cause"]
    assert reset_calls  # the broken pool was dropped for rebuild

    # With cpu_fallback=True the same failure confirms in-process instead
    # of degrading: the caller asked for definite verdicts where possible.
    results = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(64, 256), cpu_fallback=True
    )
    assert [r["valid?"] for r in results] == expect


def test_inprocess_confirm_sweep_raise_degrades_one_history(monkeypatch):
    """Advisor r4: if the confirmation worker died because sweep_analysis
    itself raises deterministically, the in-process fallback re-raises the
    same error — it must degrade THAT history to unknown, not unwind
    batch_analysis and lose every other verdict."""
    from concurrent.futures.process import BrokenProcessPool

    from jepsen_tpu.parallel import batch as pb

    hists, expect = histories_mixed(6)  # calls the real sweep; build first

    class ExplodingFuture:
        def result(self, timeout=None):
            raise BrokenProcessPool("worker died")

    class ExplodingPool:
        def submit(self, fn, *a, **kw):
            return ExplodingFuture()

    pool = ExplodingPool()
    monkeypatch.setattr(pb, "_CONFIRM_POOL", pool)
    monkeypatch.setattr(pb, "_confirm_pool", lambda workers: pool)
    monkeypatch.setattr(pb, "_reset_confirm_pool", lambda: None)

    def raising_sweep(model, hist, max_configs=None, **kw):
        raise ValueError("deterministic model bug")

    monkeypatch.setattr(pb.wgl_cpu, "sweep_analysis", raising_sweep)
    results = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(64, 256), cpu_fallback=True
    )
    assert len(results) == len(hists)
    for r, want in zip(results, expect):
        if want is True:
            assert r["valid?"] is True  # untouched verdicts survive
        else:
            assert r["valid?"] == "unknown"
            assert "confirmation sweep raised" in r["cause"]


def test_exact_escalation_none_warns_once_without_fallback():
    """Advisor r4: the round-3 behavior change (None -> no exact stages)
    is only observable to cpu_fallback=False callers as extra unknowns;
    they get a one-shot warning."""
    import warnings

    from jepsen_tpu.parallel import batch as pb

    hists = [valid_register_history(10, 2, seed=1, info_rate=0.0)]
    old = pb._WARNED_EXACT_DEFAULT
    try:
        pb._WARNED_EXACT_DEFAULT = False
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pb.batch_analysis(m.CASRegister(None), hists, capacity=64,
                              cpu_fallback=False)
            assert any("exact_escalation" in str(x.message) for x in w)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pb.batch_analysis(m.CASRegister(None), hists, capacity=64,
                              cpu_fallback=False)
            assert not w  # one-shot
        # explicit () and cpu_fallback=True never warn
        pb._WARNED_EXACT_DEFAULT = False
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pb.batch_analysis(m.CASRegister(None), hists, capacity=64,
                              cpu_fallback=False, exact_escalation=())
            pb.batch_analysis(m.CASRegister(None), hists, capacity=64,
                              cpu_fallback=True)
            assert not w
    finally:
        pb._WARNED_EXACT_DEFAULT = old


def test_carried_frontier_escalation_matches_scratch():
    """Carried-frontier escalation: resuming stragglers from their exact
    pre-loss snapshot at the next rung must agree with the oracle, and
    decide at least what a from-scratch run at the widest rung alone
    (capacity=(512,)) decides — the resolution a scratch restart gave
    (the snapshot is exact and closure is deterministic, so the wider
    rung reaches the identical frontier)."""
    from jepsen_tpu.parallel import batch as pb

    hists, expect = [], []
    # branch-heavy histories that overflow cap 16 and resolve wider
    for i in range(8):
        hist = valid_register_history(60, 6, seed=100 + i, info_rate=0.35)
        if i % 2:
            hist = corrupt(hist, seed=i)
            expect.append(wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"])
        else:
            expect.append(True)
        hists.append(hist)

    kw = dict(cpu_fallback=False, exact_escalation=())
    carried = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(16, 64, 512), **kw)
    scratch = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(512,), **kw)
    for i, (c, s, want) in enumerate(zip(carried, scratch, expect)):
        # neither ladder may ever contradict the oracle
        assert c["valid?"] in (want, "unknown"), (i, c["valid?"], want)
        assert s["valid?"] in (want, "unknown"), (i, s["valid?"], want)
    # resumption must not LOSE resolution power vs scratch
    n_unknown_carried = sum(r["valid?"] == "unknown" for r in carried)
    n_unknown_scratch = sum(r["valid?"] == "unknown" for r in scratch)
    assert n_unknown_carried <= n_unknown_scratch, (
        n_unknown_carried, n_unknown_scratch)


def test_carried_frontier_snapshot_resume_single_lane():
    """Kernel-level resume contract: run at a tiny capacity until lossy,
    then resume from the returned snapshot at a wide capacity and get the
    oracle's verdict — without re-running the verified prefix."""
    import jax.numpy as jnp

    from jepsen_tpu.ops import wgl

    hist = corrupt(
        valid_register_history(80, 6, seed=42, info_rate=0.35), seed=7)
    truth = wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"]
    packed = wgl.pack(m.CASRegister(None), hist)
    n_active = int(packed["bar_active"].sum())
    packed = wgl.pad_packed(packed)
    B, P, G, W = packed["B"], packed["P"], packed["G"], packed["W"]

    def run(cap, bptr0, st0, fo0, fc0, al0):
        T = wgl.async_ticks(B, cap)
        return wgl._run_async(
            packed["step"], cap, T, B, P, G, W,
            bptr0, st0, fo0, fc0, al0, jnp.int32(n_active),
            *packed["bar"], *packed["mov"], *packed["grp"],
            packed["grp_open"], jnp.asarray(packed["slot_lane"]),
            jnp.asarray(packed["slot_onehot"]),
        )

    bp, st, fo, fc, al = wgl.fresh_frontier(1, 4, W, G, [packed["init_state"]])
    valid, failed_at, lossy, peak, bs, sst, sfo, sfc, sal = run(
        4, bp[0], st[0], fo[0], fc[0], al[0])
    if not bool(lossy):
        import pytest
        pytest.skip("cap 4 unexpectedly sufficient; can't exercise resume")
    assert int(bs) >= 0
    import numpy as np
    bs2, rst, rfo, rfc, ral = wgl.pad_resume(
        (int(bs), np.asarray(sst), np.asarray(sfo), np.asarray(sfc),
         np.asarray(sal)), 1024, W, G)
    valid2, failed2, lossy2, _pk, *_ = run(
        1024, jnp.int32(bs2), jnp.asarray(rst), jnp.asarray(rfo),
        jnp.asarray(rfc), jnp.asarray(ral))
    if not bool(lossy2):
        got = True if bool(valid2) else (False if int(failed2) >= 0 else "unknown")
        if got is not True and got is not False:
            return
        if truth == "unknown":
            return
        assert got == truth


def test_carried_frontier_multi_chunk_stage(monkeypatch):
    """When a rung splits into several sub-batch chunks, each chunk's
    resume snapshot is fetched immediately after ITS launch (at most one
    chunk's snapshot device-resident — the resident-row bound the lane
    budget enforces) and pending lanes still resume correctly on the
    next rung.  Shrinks the lane budgets so 8 histories at cap 16 split
    into multiple chunks."""
    from jepsen_tpu.parallel import batch as pb

    monkeypatch.setattr(pb, "_CARRY_LANE_BUDGET", 48)   # 48//16 = 3 lanes/chunk
    monkeypatch.setattr(pb, "_GREEDY_LANE_BUDGET", 48)

    hists, expect = [], []
    for i in range(8):
        hist = valid_register_history(60, 6, seed=300 + i, info_rate=0.35)
        if i % 2:
            hist = corrupt(hist, seed=i)
            expect.append(wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"])
        else:
            expect.append(True)
        hists.append(hist)

    res = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(16, 64, 512),
        cpu_fallback=False, exact_escalation=(),
    )
    for i, (r, want) in enumerate(zip(res, expect)):
        assert r["valid?"] in (want, "unknown"), (i, r["valid?"], want)
    # the multi-chunk path must not lose resolution power: the wider
    # rungs decide at least the histories the single-chunk ladder does
    n_unknown = sum(r["valid?"] == "unknown" for r in res)
    assert n_unknown <= 2, [r["valid?"] for r in res]


def test_exact_scan_safe_measured_boundary():
    """Pins the chip-measured fault table (tools/repro_exact_fault.py,
    round 5): every B<=2048 cell ok; B=4096 faults at cap>=1024;
    B=8192 faults at every measured cap."""
    from jepsen_tpu.ops import wgl

    ok = [(2048, 512), (2048, 1024), (2048, 2048), (4096, 512)]
    fault = [(4096, 1024), (4096, 2048), (8192, 512), (8192, 1024),
             (8192, 2048)]
    for B, cap in ok:
        assert wgl.exact_scan_safe(B, cap), (B, cap)
    for B, cap in fault:
        assert not wgl.exact_scan_safe(B, cap), (B, cap)
    # small shapes (the batch ladder's bread and butter) are never routed
    assert wgl.exact_scan_safe(128, 2048)
    # the grid is single-lane: a vmapped launch multiplies the live
    # buffers by the (padded) lane count, so the effective width is
    # lanes*cap — 32 lanes at cap 512 on B=4096 is far off-grid
    assert not wgl.exact_scan_safe(4096, 512, lanes=32)
    assert wgl.exact_scan_safe(128, 2048, lanes=8)  # bench exact stages
    # untested headroom beyond the grid is routed conservatively:
    # B=8192 faulted at EVERY measured cap, so no capacity makes it safe
    assert not wgl.exact_scan_safe(8192, 256)
    assert not wgl.exact_scan_safe(16384, 64)
    assert not wgl.exact_scan_safe(2048, 8192)
    # the guard checks the PADDED launch shape
    assert wgl.pad_B(100) == 128 and wgl.pad_B(4096) == 4096


def test_exact_fault_guard_routes_to_chunked(monkeypatch):
    """With every shape declared unsafe, exact ladder stages must route
    through the chunked exact path and still produce oracle-correct
    verdicts (the guard changes the execution plan, never the answer)."""
    from jepsen_tpu.ops import wgl as wgl_mod
    from jepsen_tpu.parallel import batch as pb

    monkeypatch.setattr(
        wgl_mod, "exact_scan_safe", lambda B, cap, lanes=1: False)

    hists, expect = [], []
    for i in range(6):
        hist = valid_register_history(40, 5, seed=500 + i, info_rate=0.2)
        if i % 2:
            hist = corrupt(hist, seed=i)
            expect.append(wgl_cpu.sweep_analysis(m.CASRegister(None), hist)["valid?"])
        else:
            expect.append(True)
        hists.append(hist)

    # exact ladder stage: a tiny async ladder leaves stragglers for the
    # exact stage, which must use chunked_analysis under the patch
    res = pb.batch_analysis(
        m.CASRegister(None), hists, capacity=(8,), exact_escalation=(256,),
        cpu_fallback=False, confirm_refutations=False,
    )
    for i, (r, want) in enumerate(zip(res, expect)):
        assert r["valid?"] in (want, "unknown"), (i, r["valid?"], want)
