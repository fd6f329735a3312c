"""Telemetry layer tests: the obs API contract (span nesting, JSONL
round-trip, the disabled no-op fast path), checker attribution, the
batch-ladder stage table, the spans of a batch check's front (one each
a check, whatever the number of keys), and the run_test integration
(telemetry artifacts land in the store dir; disabled runs write
nothing)."""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))

from jepsen_tpu import checker as c
from jepsen_tpu import core, generator as gen, models as m, obs, store, testkit
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.obs.summary import format_summary, summarize


def read_jsonl(d):
    return [
        json.loads(line)
        for line in (pathlib.Path(d) / "telemetry.jsonl").read_text().splitlines()
        if line
    ]


# ---------------------------------------------------------------------------
# The API contract
# ---------------------------------------------------------------------------


def test_recorder_header_epoch_pid_host(tmp_path):
    """The meta header carries the wall-clock epoch t0 (plus pid/host):
    event "t" offsets are monotonic-only, so without t0 two processes'
    traces could never be time-aligned."""
    import os
    import time

    before = time.time()
    with obs.recording(tmp_path):
        obs.event("x")
    after = time.time()
    meta = read_jsonl(tmp_path)[0]
    assert meta["type"] == "meta"
    assert before <= meta["t0"] <= after
    assert meta["wall-clock"] == meta["t0"]  # legacy key stays aligned
    assert meta["pid"] == os.getpid()
    assert isinstance(meta["host"], str) and meta["host"]


def test_capture_attach_crosses_threads(tmp_path):
    """The context-handoff API: a Ctx captured on one thread re-parents
    and trace-stamps spans emitted on another (the serve admission ->
    scheduler -> demux hops)."""
    import threading

    with obs.recording(tmp_path):
        with obs.span("root"):
            ctx = obs.capture(trace="tr-1")
        assert ctx.parent == "root" and ctx.trace == "tr-1"

        def other():
            with obs.attach(ctx):
                with obs.span("hop"):
                    with obs.span("nested"):
                        pass
                obs.counter("hits")
                obs.gauge("depth", 1)
            obs.counter("outside")  # after detach: unstamped

        t = threading.Thread(target=other)
        t.start()
        t.join()
        # attach also works trace-only (the shared-batch scope)
        with obs.attach(trace=["tr-1", "tr-2"]):
            obs.event("shared")
    by_name = {e.get("name"): e for e in read_jsonl(tmp_path)[1:]}
    assert by_name["hop"]["parent"] == "root"  # the cross-thread link
    assert by_name["hop"]["trace"] == "tr-1"
    assert by_name["nested"]["parent"] == "hop"  # local nesting wins
    assert by_name["nested"]["trace"] == "tr-1"
    assert by_name["hits"]["trace"] == "tr-1"
    assert by_name["depth"]["trace"] == "tr-1"
    assert "trace" not in by_name["outside"]
    assert "trace" not in by_name["root"]
    assert by_name["shared"]["trace"] == ["tr-1", "tr-2"]


def test_span_nesting_attrs_and_jsonl_roundtrip(tmp_path):
    with obs.recording(tmp_path) as rec:
        with obs.span("outer", a=1) as sp:
            with obs.span("inner"):
                pass
            sp.set(b="two")
        obs.counter("hits", 3, tag="x")
        obs.gauge("depth", 7)
        obs.event("note", detail="d")
    events = read_jsonl(tmp_path)
    assert events[0]["type"] == "meta"
    by_name = {e.get("name"): e for e in events[1:]}
    inner, outer = by_name["inner"], by_name["outer"]
    # nesting: the inner span is emitted first and carries its parent
    assert inner["parent"] == "outer"
    assert "parent" not in outer
    assert outer["attrs"] == {"a": 1, "b": "two"}
    assert outer["dur"] >= inner["dur"] >= 0
    assert by_name["hits"]["n"] == 3 and by_name["hits"]["attrs"] == {"tag": "x"}
    assert by_name["depth"]["value"] == 7
    assert by_name["note"]["attrs"] == {"detail": "d"}
    # the rolled-up summary landed next to the JSONL and agrees with it
    rolled = json.loads((tmp_path / "telemetry.json").read_text())
    assert rolled == summarize(events) == rec.summary
    assert rolled["spans"]["outer"]["count"] == 1
    assert rolled["counters"] == {"hits": 3}
    assert rolled["gauges"] == {"depth": 7}


def test_span_exception_recorded(tmp_path):
    with obs.recording(tmp_path):
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
    ev = [e for e in read_jsonl(tmp_path) if e.get("name") == "boom"][0]
    assert ev["err"] == "ValueError"


def test_disabled_noop_path(tmp_path):
    # no recorder installed: spans are the shared singleton, nothing
    # allocates per call, counters/gauges return immediately
    assert obs.active() is None
    assert obs.span("a") is obs.span("b", x=1) is obs.NOOP_SPAN
    with obs.span("a") as sp:
        assert sp.set(k=2) is sp
    obs.counter("c")
    obs.gauge("g", 1)
    obs.event("e")
    obs.span_event("s", 0.1)
    # recording with enabled=False installs nothing and writes nothing
    with obs.recording(tmp_path / "sub", enabled=False) as rec:
        assert rec is None
        assert obs.span("x") is obs.NOOP_SPAN
        obs.counter("c")
    assert not (tmp_path / "sub").exists()


def test_recording_nests_passthrough(tmp_path):
    with obs.recording(tmp_path) as outer:
        with obs.recording(tmp_path / "inner") as inner:
            assert inner is outer
            obs.counter("both")
        # inner close must not tear down the outer recording
        assert obs.active() is outer
        obs.counter("both")
    assert not (tmp_path / "inner").exists()
    rolled = json.loads((tmp_path / "telemetry.json").read_text())
    assert rolled["counters"] == {"both": 2}


def test_new_recording_replaces_previous_stream(tmp_path):
    """Re-analyzing a stored run must not append a second event stream
    that the summarizer double-counts (jsonl is the source of truth)."""
    with obs.recording(tmp_path):
        obs.counter("hits")
    with obs.recording(tmp_path):
        obs.counter("hits")
    events = read_jsonl(tmp_path)
    assert sum(1 for e in events if e.get("type") == "meta") == 1
    assert summarize(events)["counters"] == {"hits": 1}
    rolled = json.loads((tmp_path / "telemetry.json").read_text())
    assert rolled["counters"] == {"hits": 1}


def test_env_toggle(monkeypatch):
    monkeypatch.delenv(obs.ENV_VAR, raising=False)
    assert obs.env_enabled(True) and not obs.env_enabled(False)
    for off in ("0", "false", "off", "NO"):
        monkeypatch.setenv(obs.ENV_VAR, off)
        assert not obs.env_enabled(True)
    monkeypatch.setenv(obs.ENV_VAR, "1")
    assert obs.env_enabled(False)
    # test-map key wins over env
    assert not obs.enabled_for({"telemetry?": False})
    monkeypatch.setenv(obs.ENV_VAR, "0")
    assert obs.enabled_for({"telemetry?": True})
    assert not obs.enabled_for({})


def test_noop_fast_path_overhead_guard():
    """With telemetry off (no recorder, mirror off), the per-call cost of
    span/counter/gauge must stay negligible — the kernels' host loops
    call these unguarded.  The bound is deliberately generous (CI noise,
    cold caches); a regression that installs real per-call work (dict
    allocation, lock acquisition, registry writes) blows through it."""
    import time

    from jepsen_tpu.obs import metrics

    assert obs.active() is None
    saved = metrics.MIRROR
    metrics.enable_mirror(False)
    try:
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("s", a=1):
                pass
            obs.counter("c")
            obs.gauge("g", 1)
            obs.span_event("e", 0.1)
        per_call = (time.perf_counter() - t0) / (4 * n)
    finally:
        metrics.enable_mirror(saved)
    assert per_call < 10e-6, f"no-op telemetry path costs {per_call*1e6:.2f}µs/call"


def test_summarize_edge_cases_empty_sections():
    """summarize() on empty/partial streams: every section present with
    its empty shape (consumers index unconditionally), no serve/faults
    rows invented, and the text renderer skips absent blocks."""
    s = summarize([])
    assert s["wall_s"] == 0
    assert s["phases"] == [] and s["checkers"] == [] and s["ladder"] == []
    assert s["serve"] == {} and s["faults"] == [] and s["dedup"] == []
    assert s["counters"] == {} and s["gauges"] == {} and s["spans"] == {}
    txt = format_summary(s)
    assert "check service" not in txt and "faults" not in txt
    assert "ladder stages" not in txt
    # meta-only (a recording that opened and crashed before any event)
    s2 = summarize([{"type": "meta", "version": 1}])
    assert s2["serve"] == {} and s2["faults"] == []
    # events with no serve/fault activity leave those sections empty
    s3 = summarize([
        {"type": "span", "name": "phase.analyze", "t": 0.0, "dur": 1.5},
        {"type": "counter", "name": "hits", "t": 0.1, "n": 2},
        {"type": "gauge", "name": "depth", "t": 0.2, "value": 7},
        {"type": "span", "name": "x", "t": 0.0},  # dur absent -> 0
        {"type": "counter", "name": "k", "t": None},  # t absent -> 0
    ])
    assert s3["serve"] == {} and s3["faults"] == []
    assert s3["phases"] == [{"phase": "analyze", "wall_s": 1.5, "count": 1}]
    assert s3["counters"] == {"hits": 2, "k": 1}
    assert "phases" in format_summary(s3)


def test_metrics_registry_and_obs_mirror():
    """The live registry: labeled counters/gauges/histograms render as
    valid Prometheus text, and the obs mirror feeds it (by name) even
    with NO recording active — the serving process's regime."""
    from jepsen_tpu.obs import metrics

    r = metrics.Registry()
    r.inc("serve.verdicts", verdict="true")
    r.inc("serve.verdicts", 2, verdict="false")
    r.set("serve.queue_depth", 4)
    r.set("weird.gauge", "not-a-number")  # non-numeric: never rendered
    r.set("bool.gauge", True)
    r.observe("lat", 0.004, buckets=(0.01, 1.0))
    r.observe("lat", 5.0, buckets=(0.01, 1.0))
    text = r.render()
    assert "# TYPE jepsen_tpu_serve_verdicts_total counter" in text
    assert 'jepsen_tpu_serve_verdicts_total{verdict="false"} 2' in text
    assert "# TYPE jepsen_tpu_serve_queue_depth gauge" in text
    assert "jepsen_tpu_serve_queue_depth 4" in text
    assert "weird_gauge" not in text
    assert "jepsen_tpu_bool_gauge 1" in text
    assert 'jepsen_tpu_lat_bucket{le="0.01"} 1' in text
    assert 'jepsen_tpu_lat_bucket{le="+Inf"} 2' in text
    assert "jepsen_tpu_lat_sum 5.004" in text
    assert "jepsen_tpu_lat_count 2" in text
    assert r.get("serve.queue_depth") == 4
    assert r.get("serve.verdicts", verdict="true") == 1
    assert r.get("nope") is None
    snap = r.snapshot()
    assert snap["histograms"]["jepsen_tpu_lat"]["count"] == 2
    r.reset()
    assert r.render() == ""
    # --- the obs mirror: counters/gauges land with no recorder ---
    saved = metrics.MIRROR
    before = metrics.REGISTRY.get("mirror.test.hits") or 0
    try:
        metrics.enable_mirror(False)
        obs.counter("mirror.test.hits", 5)
        assert (metrics.REGISTRY.get("mirror.test.hits") or 0) == before
        metrics.enable_mirror(True)
        assert obs.observing()
        obs.counter("mirror.test.hits", 5)
        obs.gauge("mirror.test.depth", 9)
        assert metrics.REGISTRY.get("mirror.test.hits") == before + 5
        assert metrics.REGISTRY.get("mirror.test.depth") == 9
    finally:
        metrics.enable_mirror(saved)


def test_profiler_hook_bounded_exclusive_generation_safe(tmp_path, monkeypatch):
    """The jax.profiler capture hook: bounded (seconds clamp to
    max_seconds), exclusive (second start reports, never corrupts), and
    a stale watchdog (its capture already stopped manually) must no-op
    instead of truncating the NEXT capture."""
    from jepsen_tpu.obs import profiler

    calls = []
    monkeypatch.setattr(
        profiler, "_trace_api",
        lambda: (lambda d: calls.append(("start", d)),
                 lambda: calls.append(("stop",))),
    )
    h = profiler.ProfilerHook(tmp_path, max_seconds=60)
    doc = h.start(5)
    assert doc["profiling"] is True and doc["seconds"] == 5
    assert doc["capture_dir"].startswith(str(tmp_path))
    assert h.start()["error"] == "capture already running"
    stale_gen = h._gen
    st = h.stop()
    assert st["profiling"] is False and "stopped" in st
    assert h.stop()["profiling"] is False  # idempotent
    # stale watchdog vs a new capture: the gen mismatch no-ops
    h.start(5)
    assert h.stop(gen=stale_gen)["profiling"] is True  # still running
    assert h.stop()["profiling"] is False
    # the bound clamps over-asks
    assert h.start(999)["seconds"] == 60
    h.stop()
    assert [c[0] for c in calls] == ["start", "stop"] * 3


def test_trace_export_lanes_and_counters(tmp_path):
    """The Perfetto export: one lane per request trace id, one lane per
    DEVICE (device-attributed launches render once per member device),
    shared-batch spans on the ladder lane with their member ids in
    args, counter tracks on their own dedicated lane (incl. one per
    latency-class queue) — and the CLI wrapper round-trips."""
    import trace_export

    from jepsen_tpu.obs.trace import read_jsonl_events, to_trace_events

    with obs.recording(tmp_path):
        with obs.attach(trace="req-1"):
            obs.span_event("serve.admission", 0.01, client="a")
        with obs.attach(trace="req-2"):
            obs.span_event("serve.admission", 0.02, client="b")
        with obs.span("serve.batch", trace_ids=["req-1", "req-2"]):
            with obs.attach(trace=["req-1", "req-2"]):
                obs.span_event("ladder.stage", 0.1, stage=0)
                obs.span_event("ladder.launch", 0.08, engine="async",
                               devices=[0, 3])
                obs.gauge("device.buffer_bytes", 1234)
        obs.gauge("serve.queue_depth", 2)
        obs.gauge("serve.queue_depth.interactive", 1)
        obs.gauge("serve.queue_depth.batch", 1)
    events, skipped = read_jsonl_events(tmp_path / "telemetry.jsonl")
    assert skipped == 0
    trace = to_trace_events(events, skipped_lines=skipped)
    evs = trace["traceEvents"]
    lane_names = {
        e["args"]["name"]: e["tid"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert lane_names["request req-1"] != lane_names["request req-2"]
    assert lane_names["ladder/shared"] == 0
    assert trace["otherData"]["requests"] == 2
    assert trace["otherData"]["devices"] == 2
    assert trace["otherData"]["skipped_lines"] == 0
    adm = [e for e in evs if e["ph"] == "X" and e["name"] == "serve.admission"]
    assert {e["tid"] for e in adm} == {
        lane_names["request req-1"], lane_names["request req-2"]}
    [stage] = [e for e in evs if e["ph"] == "X" and e["name"] == "ladder.stage"]
    assert stage["tid"] == 0 and stage["args"]["trace"] == ["req-1", "req-2"]
    # the device-attributed launch renders once per member device, on
    # per-device lanes with stable sort indexes
    launches = [e for e in evs
                if e["ph"] == "X" and e["name"] == "ladder.launch"]
    assert {e["tid"] for e in launches} == {
        lane_names["device 0"], lane_names["device 3"]}
    sort_idx = {
        e["tid"]: e["args"]["sort_index"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_sort_index"
    }
    assert sort_idx[lane_names["device 0"]] < sort_idx[lane_names["device 3"]]
    # counter tracks ride their own lane, never the ladder/device lanes
    counter_evs = [e for e in evs if e["ph"] == "C"]
    counters = {e["name"] for e in counter_evs}
    assert {"serve.queue_depth", "device.buffer_bytes",
            "serve.queue_depth.interactive",
            "serve.queue_depth.batch"} <= counters
    assert {e["tid"] for e in counter_evs} == {lane_names["counters"]}
    assert trace["otherData"]["t0"] is not None
    # the CLI writes a loadable trace.json next to the jsonl
    assert trace_export.main([str(tmp_path)]) == 0
    out = json.loads((tmp_path / "trace.json").read_text())
    assert out["traceEvents"]
    assert trace_export.main([str(tmp_path / "missing")]) == 1


def test_trace_summarize_partial_stream(tmp_path, capsys):
    """A partially-written telemetry.jsonl (crash mid-line) summarizes
    what parsed; unreadable inputs exit 1 with a message, never a
    traceback (the satellite contract)."""
    import trace_summarize

    p = tmp_path / "telemetry.jsonl"
    p.write_text(
        '{"type":"meta","version":1,"t0":1.0,"pid":1}\n'
        '{"type":"counter","name":"hits","t":0.1,"n":3}\n'
        '{"type":"span","name":"phase.run","t":0.0,"dur"'  # truncated
    )
    assert trace_summarize.main([str(tmp_path)]) == 0
    captured = capsys.readouterr()
    assert "counters" in captured.out
    assert "skipped 1 malformed line" in captured.err
    # --json still works on the tolerant load, and the summary carries
    # the skip count (telemetry.skipped_lines — the satellite contract)
    assert trace_summarize.main([str(p), "--json"]) == 0
    rolled = json.loads(capsys.readouterr().out)
    assert rolled["counters"] == {"hits": 3}
    assert rolled["telemetry"]["skipped_lines"] == 1
    # nothing parseable -> clear error, exit 1
    bad = tmp_path / "bad" / "telemetry.jsonl"
    bad.parent.mkdir()
    bad.write_text("not json at all\n{{{\n")
    assert trace_summarize.main([str(bad)]) == 1
    assert "no parseable telemetry" in capsys.readouterr().err
    # empty file -> clear error, exit 1
    empty = tmp_path / "empty" / "telemetry.jsonl"
    empty.parent.mkdir()
    empty.write_text("")
    assert trace_summarize.main([str(empty)]) == 1
    # corrupt rolled-up .json -> clear error, exit 1
    rolled = tmp_path / "rolled"
    rolled.mkdir()
    (rolled / "telemetry.json").write_text('{"version": 1, "wall_s"')
    assert trace_summarize.main([str(rolled)]) == 1
    assert "not valid JSON" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Checker attribution (check_safe / Compose)
# ---------------------------------------------------------------------------


class Boom(c.Checker):
    def check(self, test, history, opts):
        raise RuntimeError("kaboom")


def test_check_safe_names_failing_checker():
    r = c.check_safe(Boom(), {}, [])
    assert r["valid?"] == c.UNKNOWN
    assert r["checker"] == "Boom"
    assert "kaboom" in r["error"]
    # an explicit name (the Compose map key) wins
    r2 = c.check_safe(Boom(), {}, [], name="linear")
    assert r2["checker"] == "linear"


def test_compose_attributes_errors_and_emits_spans(tmp_path):
    comp = c.compose({"bad": Boom(), "good": c.unbridled_optimism()})
    with obs.recording(tmp_path):
        r = comp.check({}, [], {})
    assert r["valid?"] == c.UNKNOWN
    assert r["bad"]["checker"] == "bad"
    events = read_jsonl(tmp_path)
    spans = {
        e["attrs"]["checker"]: e
        for e in events
        if e.get("name") == "checker.check"
    }
    assert spans["bad"]["attrs"]["valid"] == "unknown"
    assert spans["good"]["attrs"]["valid"] is True
    counts = [e for e in events if e.get("name") == "checker.errors"]
    assert len(counts) == 1 and counts[0]["attrs"] == {"checker": "bad"}
    rolled = summarize(events)
    assert {ck["checker"]: ck["valid"] for ck in rolled["checkers"]} == {
        "bad": "unknown", "good": True,
    }


# ---------------------------------------------------------------------------
# Ladder-stage telemetry (parallel.batch_analysis)
# ---------------------------------------------------------------------------


def _mixed_histories(n=6):
    from genhist import corrupt, valid_register_history

    hists = []
    for i in range(n):
        hh = valid_register_history(24, 3, seed=i, info_rate=0.2)
        if i % 3 == 2:
            hh = corrupt(hh, seed=i)
        hists.append(hh)
    return hists


def test_batch_analysis_stage_table(tmp_path):
    from jepsen_tpu.parallel import batch_analysis

    with obs.recording(tmp_path):
        batch_analysis(m.CASRegister(None), _mixed_histories(), capacity=(16, 64))
    rolled = json.loads((tmp_path / "telemetry.json").read_text())
    ladder = rolled["ladder"]
    assert ladder, "expected ladder.stage rows"
    for row in ladder:
        assert row["engine"] in ("greedy", "async", "sync", "exact")
        assert row["capacity"] >= 1 and row["lanes"] >= 1
        assert row["launches"] >= 1
        assert "unknowns_remaining" in row
        # the compile/execute split accounts for every launch
        assert row["compile_launches"] + (
            row["launches"] - row["compile_launches"]
        ) == row["launches"]
    assert ladder[-1]["unknowns_remaining"] == 0
    assert rolled["gauges"]["ladder.unknowns_remaining"] == 0
    assert rolled["spans"]["ladder.pack"]["count"] == 1
    # the table renders
    assert "ladder stages" in format_summary(rolled)


def test_batch_analysis_unknowns_observable(tmp_path):
    """exact_escalation=None + cpu_fallback=False unknowns carry an
    attributable cause and a final unknowns-remaining gauge (the
    documented 'no runtime signal' gap)."""
    from jepsen_tpu.parallel import batch_analysis

    with obs.recording(tmp_path):
        results = batch_analysis(
            m.CASRegister(None), _mixed_histories(), capacity=(2,),
            cpu_fallback=False, exact_escalation=(),
            confirm_refutations=False, greedy_first=False,
        )
    unknowns = [r for r in results if r["valid?"] == "unknown"]
    assert unknowns, "tiny capacity should leave unknowns"
    for r in unknowns:
        assert "capacity ladder (2,) exhausted" in r["cause"]
        assert "exact-escalation" in r["cause"]
    rolled = json.loads((tmp_path / "telemetry.json").read_text())
    assert rolled["gauges"]["ladder.unknowns_remaining"] == len(unknowns)


# ---------------------------------------------------------------------------
# The batch check's front (independent.checker over linearizable)
# ---------------------------------------------------------------------------

#: the spans directly under a batch check's ``checker.check``
FRONT = ("independent.split", "ladder.run", "checker.evidence",
         "independent.save")
#: what the ladder emits once per launch or per stage
PER_LAUNCH = ("ladder.launch", "ladder.stage", "ladder.compile_cache.hit",
              "ladder.compile_cache.miss", "wgl.runner_cache.hit",
              "wgl.runner_cache.miss")
#: the ladder's dedup probe, once per shape per process
PER_SHAPE = ("ladder.dedup-probe", "dedup.round")


def _keyed_history(n_keys):
    """``n_keys`` keys of ``_mixed_histories``' shape, one in three
    corrupted, each key with processes of its own."""
    from jepsen_tpu import independent

    out = []
    for k, hh in enumerate(_mixed_histories(n_keys)):
        out += [{**o, "process": o["process"] + 3 * k,
                 "value": independent.tuple_(k, o["value"])} for o in hh]
    return [{**o, "index": i} for i, o in enumerate(out)]


def _batch_checker():
    from jepsen_tpu import independent

    return independent.checker(linearizable(
        {"model": m.CASRegister(None), "kernel-opts": {"capacity": (64,)}}))


@pytest.fixture(scope="module")
def front_checks(tmp_path_factory):
    """Recorded batch checks of 8 and of 40 keys: ``{keys: (result,
    events)}``."""
    out = {}
    for n in (8, 40):
        d = tmp_path_factory.mktemp(f"front{n}")
        with obs.recording(d) as rec:
            res = c.check_safe(_batch_checker(), {}, _keyed_history(n))
        out[n] = (res, list(rec.events))
    return out


def test_front_spans_once_each_under_the_check(front_checks):
    for n, (res, events) in front_checks.items():
        assert res["valid?"] is False and len(res["failures"]) == n // 3
        spans = [e for e in events if e["type"] == "span"]
        [check] = [e for e in spans if e["name"] == "checker.check"]
        for name in FRONT:
            [s] = [e for e in spans if e["name"] == name]
            assert s["parent"] == "checker.check"
        by = {e["name"]: e["attrs"] for e in spans if e["name"] in FRONT}
        assert by["independent.split"] == {
            "keys": n, "events": sum(len(h) for h in _mixed_histories(n)),
            "unkeyed": 0}
        assert by["ladder.run"] == {"histories": n}
        # the evidence span's parts: seconds summed over its keys
        [ev] = [e for e in spans if e["name"] == "checker.evidence"]
        parts = ("fingerprint_s", "witness_s", "digest_s")
        assert set(ev["attrs"]) == {"histories", "stored", *parts}
        assert (ev["attrs"]["histories"], ev["attrs"]["stored"]) == (n, False)
        assert all(ev["attrs"][k] >= 0 for k in parts)
        assert sum(ev["attrs"][k] for k in parts) <= ev["dur"]
        assert by["independent.save"] == {"keys": n, "stored": False}
        assert check["attrs"]["valid"] is False


def test_front_split_counts_unkeyed_ops(front_checks, tmp_path):
    """Nemesis ops land in every key's subhistory; the split span counts
    them once a check, and the verdicts stay those of the keyed ops."""
    hist = _keyed_history(8)
    nemesis = {"type": "info", "f": "kill", "value": None,
               "process": "nemesis"}
    hist = hist[:1] + [nemesis] + hist[1:len(hist) // 2] + [nemesis] \
        + hist[len(hist) // 2:]
    hist = [{**o, "index": i} for i, o in enumerate(hist)]
    with obs.recording(tmp_path) as rec:
        res = c.check_safe(_batch_checker(), {}, hist)
    [split] = [e for e in rec.events if e.get("name") == "independent.split"]
    assert split["attrs"] == {"keys": 8, "events": len(hist), "unkeyed": 2}
    assert res["failures"] == front_checks[8][0]["failures"]


def test_front_spans_cover_the_check(front_checks):
    for _res, events in front_checks.values():
        spans = [e for e in events if e["type"] == "span"]
        [check] = [e for e in spans if e["name"] == "checker.check"]
        covered, end = 0.0, float("-inf")
        for a, b in sorted((e["t"], e["t"] + e["dur"]) for e in spans
                           if e["name"] in FRONT):
            covered += max(0.0, b - max(a, end))
            end = max(end, b)
        assert covered >= 0.9 * check["dur"]


def test_front_emits_nothing_per_key(front_checks):
    """Past the ladder's per-launch, per-stage and per-shape telemetry,
    a check of 40 keys emits as many spans, counters and events as one
    of 8 with the same ladder stages; gauges are samples and not
    counted."""
    def tally(events):
        out: dict = {}
        for e in events:
            if e["type"] in ("span", "counter", "event") \
                    and e["name"] not in PER_LAUNCH + PER_SHAPE:
                out[e["name"]] = out.get(e["name"], 0) + 1
        return out

    def stages(events):
        return [(e["attrs"]["engine"], e["attrs"]["capacity"])
                for e in events if e.get("name") == "ladder.stage"]

    (_, few), (_, many) = front_checks[8], front_checks[40]
    assert stages(few) == stages(many)
    assert tally(few) == tally(many)


def test_no_greedy_walk_span(front_checks, tmp_path):
    from jepsen_tpu.checker import wgl_cpu

    for _res, events in front_checks.values():
        assert not [e for e in events if e.get("name") == "wgl_cpu.greedy_walk"]
    with obs.recording(tmp_path) as rec:
        order: list = []
        hh = _mixed_histories(1)[0]
        assert wgl_cpu.greedy_walk(m.CASRegister(None), hh, record=order)
    assert order and [e["type"] for e in rec.events] == ["meta"]


def test_front_without_recorder_emits_nothing(front_checks, monkeypatch):
    """With no recording, the front's call sites take the no-op path: no
    span object is made and nothing is emitted."""
    made, emitted = [], []

    class Spy(obs._Span):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs, "_Span", Spy)
    monkeypatch.setattr(obs.Recorder, "emit",
                        lambda self, ev: emitted.append(ev))
    assert obs.active() is None
    res = c.check_safe(_batch_checker(), {}, _keyed_history(8))
    assert res["valid?"] == front_checks[8][0]["valid?"]
    assert made == [] and emitted == []


# ---------------------------------------------------------------------------
# run_test integration (dummy client, full lifecycle)
# ---------------------------------------------------------------------------


def _base_test(tmp_path, **kw):
    def one():
        import random

        rng = random.Random(11)
        if rng.random() < 0.5:
            return {"f": "read"}
        return {"f": "write", "value": rng.randint(0, 4)}

    t = testkit.noop_test(
        name="obs-test",
        concurrency=3,
        client=testkit.atom_client(),
        generator=gen.clients(gen.limit(30, gen.repeat(one))),
        checker=c.compose(
            {
                "stats": c.stats(),
                "linear": linearizable(
                    {"model": m.CASRegister(None), "algorithm": "wgl"}
                ),
            }
        ),
    )
    t["store-dir"] = str(tmp_path / "store")
    t.update(kw)
    return t


def test_run_test_writes_telemetry_artifacts(tmp_path):
    completed = core.run_test(_base_test(tmp_path))
    d = store.test_dir(completed)
    assert (d / "telemetry.jsonl").exists()
    rolled = json.loads((d / "telemetry.json").read_text())
    phases = [p["phase"] for p in rolled["phases"]]
    for expected in ("db-cycle", "run-case", "save-history", "snarf-logs",
                     "teardown", "analyze", "save-results"):
        assert expected in phases, f"missing phase {expected}: {phases}"
    checkers = {ck["checker"]: ck for ck in rolled["checkers"]}
    assert checkers["stats"]["valid"] is True
    assert checkers["linear"]["valid"] is True
    assert all(ck["seconds"] >= 0 for ck in rolled["checkers"])
    # the telemetry-backed checker-time artifact rides along
    assert (d / "checker-times.svg").exists()
    svg = (d / "checker-times.svg").read_text()
    assert "stats" in svg and "linear" in svg
    # the web run page renders the phase table
    from jepsen_tpu import web

    page = web.telemetry_html(d)
    assert "run-case" in page
    assert "phases" in page and "checkers" in page


def test_run_test_telemetry_disabled_writes_nothing(tmp_path):
    completed = core.run_test(_base_test(tmp_path, **{"telemetry?": False}))
    assert completed["results"]["valid?"] is True
    d = store.test_dir(completed)
    assert not (d / "telemetry.jsonl").exists()
    assert not (d / "telemetry.json").exists()
    assert not (d / "checker-times.svg").exists()


def test_standalone_analyze_records_telemetry(tmp_path):
    completed = core.run_test(_base_test(tmp_path, **{"telemetry?": False}))
    loaded = store.latest(store_dir=completed["store-dir"])
    loaded["store-dir"] = completed["store-dir"]
    # the stored test map carries the run's telemetry?=False; analyze
    # honors it, so the re-check flips it back on explicitly
    loaded["telemetry?"] = True
    loaded["checker"] = linearizable(
        {"model": m.CASRegister(None), "algorithm": "sweep"}
    )
    core.analyze(loaded)
    d = store.test_dir(loaded)
    rolled = json.loads((d / "telemetry.json").read_text())
    assert [p["phase"] for p in rolled["phases"]][0] == "analyze"
    # the sweep engine's frontier stats came through the span
    assert rolled["spans"].get("wgl_cpu.sweep", {}).get("count", 0) >= 1


def test_trace_summarize_cli(tmp_path, capsys):
    import trace_summarize

    completed = core.run_test(_base_test(tmp_path))
    d = store.test_dir(completed)
    assert trace_summarize.main([str(d)]) == 0
    out = capsys.readouterr().out
    assert "phases" in out and "checkers" in out
    assert trace_summarize.main([str(d / "telemetry.json"), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["version"] == 1
    assert trace_summarize.main([str(tmp_path / "nope")]) == 1
